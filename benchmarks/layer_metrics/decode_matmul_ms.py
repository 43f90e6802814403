"""Device time of the weight matmuls in one decode step: the regions the
run's family lists as `MATMUL_SCOPES` (for the llama family `attn.qkv`,
`attn.out`, `mlp`, `moe.router`, `moe.experts` of models/llama.py::_block,
_moe_ffn, and `lm_head` of forward), median over the executions of
jit_decode in the traced window. What the weight stream alone would take
is `decode_weights_hbm_share` of this."""
from benchmarks.harness import trace_scopes as TS


def read(run):
    scopes = getattr(run.get("family"), "MATMUL_SCOPES", None)
    if not scopes:
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, tuple(scopes))
