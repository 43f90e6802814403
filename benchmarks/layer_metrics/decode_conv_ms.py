"""Device time one decode step spends in the gated short convolutions: the
regions `conv.in` (the input projection and the gate B * X), `conv.state`
(a slot's rows read, the taps, the rows written back: ops/kvcache.py::
conv_read_and_update, models/lfm2_moe.py::_short_conv) and `conv.out` (the
gate C * v and the output projection) in jit_decode. Median over the
executions of jit_decode in the traced window. Nothing where the program
opens none of the three."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("conv.in", "conv.state", "conv.out")


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or not any(s in p["scopes"] for s in SCOPES):
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, SCOPES)
