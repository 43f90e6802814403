"""Device time of the weight matmuls in one decode step: the regions
`attn.qkv`, `attn.out`, `mlp`, `moe.router`, `moe.experts` (models/llama.py
::_block, _moe_ffn) and `lm_head` (forward), median over the executions of
jit_decode in the traced window. What the weight stream alone would take
is `decode_weights_hbm_share` of this."""
from benchmarks.harness import trace_scopes as TS

MATMUL_SCOPES = ("attn.qkv", "attn.out", "mlp", "moe.router", "moe.experts",
                 "lm_head")


def read(run):
    return TS.scope_ms(TS.of_run(run), TS.DECODE, MATMUL_SCOPES)
