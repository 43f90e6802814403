"""Device time one decode step spends in the retention layers' state: the
region `ret.state` in jit_decode (every slot's `S` and `z` read, decayed,
updated and written back, the read-out and the normaliser:
ops/kvcache.py::retention_read_and_update, ops/retention.py::step). Median
over the executions of jit_decode in the traced window. Nothing where the
program opens no such region."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("ret.state",)


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or not any(s in p["scopes"] for s in SCOPES):
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, SCOPES)
