"""The closed vocabulary of `jax.named_scope` regions in the model step.

A scope only writes HLO metadata (`op_name`): the compiled code is the
same with or without it, so the names are always on. A profiler trace
shows them in every device op's name stack, which is how a trace of one
commit is compared with a trace of the next after the compiler has
renumbered every fusion (docs/observability.md "Device regions").

Scopes nest only as listed: everything from NORM to MOE_EXPERTS sits
inside LAYERS; EMBED, LM_HEAD and SAMPLE sit beside it. A reader charges
an op to the innermost name on its stack. This module imports nothing, so
models/ and ops/ take the names without pulling in anything else.
"""

EMBED = "embed"              # token embedding gather
LAYERS = "layers"            # the scan over layers; its own time is what the
# scan moves: a layer's pool sliced out of / written back into the stacked
# pool, compiler-inserted copies of the carry
NORM = "norm"                # both norms of a block
ATTN_QKV = "attn.qkv"        # q/k/v projections (+ LoRA deltas), RoPE
KV_WRITE = "kv.write"        # the scatter of the new K/V rows into the cache
KV_GATHER = "kv.gather"      # a paged context gathered through the block
# table (and int8 dequantisation of the gathered rows)
ATTN_CORE = "attn.core"      # scores, softmax, values: XLA or a kernel
ATTN_OUT = "attn.out"        # output projection
MLP = "mlp"                  # dense feed-forward
MOE_ROUTER = "moe.router"    # routing logits, top-k, mixing weights
MOE_EXPERTS = "moe.experts"  # expert matmuls and the combine
LM_HEAD = "lm_head"          # final norm and logits
SAMPLE = "sample"            # RNG split and ops/sampling.py::sample

ALL = (
    EMBED, LAYERS, NORM, ATTN_QKV, KV_WRITE, KV_GATHER, ATTN_CORE, ATTN_OUT,
    MLP, MOE_ROUTER, MOE_EXPERTS, LM_HEAD, SAMPLE,
)
