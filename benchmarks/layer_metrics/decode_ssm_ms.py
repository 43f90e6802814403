"""Device time one decode step spends in the Mamba-2 layers' state: the
region `ssm.state` in jit_decode (the step size and the decay, every slot's
`S` read, decayed, updated and written back, the read-out and `D`:
ops/kvcache.py::ssm_read_and_update; on a TPU one `ssm_state_step` a layer,
ops/ssd_kernel.py). Median over the executions of jit_decode in the traced
window. Nothing where the program opens no such region."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("ssm.state",)


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or not any(s in p["scopes"] for s in SCOPES):
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, SCOPES)
