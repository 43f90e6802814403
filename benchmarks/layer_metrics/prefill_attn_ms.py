"""Device time of the regions `kv.gather` + `attn.core` in one execution
of the prefill-chunk program at the largest bucket (the compiled program of
jit__chunk_prefill_jit that runs longest, as prefill_chunk_ms reads it):
the chunk's attention against the whole block table, median over the
traced window."""
from benchmarks.harness import trace_scopes as TS


def read(run):
    return TS.scope_ms(TS.of_run(run), TS.CHUNK, ("kv.gather", "attn.core"))
