"""Device time of the region `attn.core` in one decode step: scores,
softmax and values over the gathered context (models/llama.py::_block
opens the scope around ops/attention.py or the decode kernel), median over
the executions of jit_decode in the traced window."""
from benchmarks.harness import trace_scopes as TS


def read(run):
    return TS.scope_ms(TS.of_run(run), TS.DECODE, ("attn.core",))
