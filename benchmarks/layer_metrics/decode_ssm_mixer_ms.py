"""Device time one decode step spends in everything a Mamba-2 layer adds:
the regions `ssm.in` (the input projection), `conv.state` (a slot's rows
read, the taps, their bias and SiLU, the rows written back), `ssm.state`
(`decode_ssm_ms`) and `ssm.out` (the gated norm and the output projection)
in jit_decode (models/granitemoehybrid.py::_mixer). Median over the
executions of jit_decode in the traced window. Nothing where the program
opens no `ssm.` region."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("ssm.in", "conv.state", "ssm.state", "ssm.out")


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or not any(s in p["scopes"] for s in SCOPES
                            if s.startswith("ssm.")):
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, SCOPES)
