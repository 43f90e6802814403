"""Device time of the region `kv.gather` in one decode step: the context
of `max_batch x max_seq_len` positions gathered through the block table
(ops/kvcache.py::paged_update_and_read opens the scope), median over the
executions of jit_decode in the traced window."""
from benchmarks.harness import trace_scopes as TS


def read(run):
    return TS.scope_ms(TS.of_run(run), TS.DECODE, ("kv.gather",))
