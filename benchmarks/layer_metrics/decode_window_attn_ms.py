"""Device time one decode step spends on the layers that keep a window of
history: the regions `kv.ring` (a slot's ring read, the new row scattered:
ops/kvcache.py::ring_read_and_update) and `attn.window` (scores, softmax,
values over the ring: models/exaone_moe.py::window_attention). Median over
the executions of jit_decode in the traced window. Nothing where the
program opens neither region."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("kv.ring", "attn.window")


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or not any(s in p["scopes"] for s in SCOPES):
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, SCOPES)
