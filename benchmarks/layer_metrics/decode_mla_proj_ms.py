"""Device time one decode step spends in the projections of multi-head
latent attention: the regions `attn.qkv` (the two down-projections, their
norms, W_UQ, the rotations), `attn.absorb` (q_nope through W_UK^T into the
latent's space, the read-out back through W_UV) and `attn.out` (W_O) of
models/deepseek_v3.py::_block, median over the executions of jit_decode in
the traced window. Nothing where the program opens no `attn.absorb`."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("attn.qkv", "attn.absorb", "attn.out")


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or "attn.absorb" not in p["scopes"]:
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, SCOPES)
