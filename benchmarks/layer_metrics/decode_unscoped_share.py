"""Share of a decode step's device time in ops that carry no name of the
region vocabulary (substratus_tpu/ops/scopes.py), from the traced run: what
a per-region breakdown of the step cannot place."""
from benchmarks.harness import trace_scopes as TS


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None:
        return None
    total = sum(v["ms"] for v in p["scopes"].values())
    return 100.0 * p["scopes"].get(TS.UNSCOPED, {"ms": 0.0})["ms"] / total
