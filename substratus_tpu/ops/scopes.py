"""The closed vocabulary of `jax.named_scope` regions in the model step.

A scope only writes HLO metadata (`op_name`): the compiled code is the
same with or without it, so the names are always on. A profiler trace
shows them in every device op's name stack, which is how a trace of one
commit is compared with a trace of the next after the compiler has
renumbered every fusion (docs/observability.md "Device regions").

Scopes nest only as listed: everything from NORM to MOE_SHARED sits
inside LAYERS; EMBED, LM_HEAD and SAMPLE sit beside it. A reader charges
an op to the innermost name on its stack. This module imports nothing, so
models/ and ops/ take the names without pulling in anything else.

A layer that keeps a window of history (models/exaone_moe.py) opens
KV_RING and ATTN_WINDOW where a layer that keeps all of it opens KV_WRITE,
KV_GATHER and ATTN_CORE, so the two kinds of history never share a row of
a trace's table. A layer whose operator is a gated short convolution
(models/lfm2_moe.py) opens CONV_IN, CONV_STATE and CONV_OUT where an
attention layer opens the ATTN_ and KV_ names. A layer whose operator is
power retention (models/brumby.py) keeps a state a decode slot and no
history at all: it opens RET_STATE and RET_INTRA between ATTN_QKV and
ATTN_OUT. A layer whose attention is latent (models/deepseek_v3.py)
keeps one shared row a token in the pages: a decode step opens
ATTN_ABSORB around ATTN_CORE (the queries carried into the latent's space
and the read-out carried back), a chunk ATTN_EXPAND ahead of it (what of
the latents' expansion into keys and values stays outside the kernel).
Where such a layer picks the rows a query attends by a learned index
(ops/sparse_index.py) it opens ATTN_INDEX and ATTN_SELECT between ATTN_QKV
and ATTN_CORE, and ATTN_CORE then holds the read of the picked rows. A layer whose
operator is a Mamba-2 mixer (models/granitemoehybrid.py) keeps a state a
decode slot beside its convolution's rows: it opens SSM_IN, the held
CONV_STATE (the rows, the taps, their bias and SiLU), SSM_STATE, in a chunk
SSM_INTRA (inside SSM_STATE, whose region holds the chunk's scan: the one
pair that nests, as RET_INTRA in RET_STATE), and SSM_OUT where an attention
layer opens the ATTN_ and KV_ names.
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
MOE_SHARED = "moe.shared"    # the shared expert's matmuls
KV_RING = "kv.ring"          # a window layer's per-slot ring: the read of its
# rows and the scatter of the new ones
ATTN_WINDOW = "attn.window"  # scores, softmax, values of a window layer
CONV_IN = "conv.in"          # a convolution layer's input projection and
# the gate B * X
CONV_STATE = "conv.state"    # the read of the slot's rows, the taps, the rows
# written back (ops/kvcache.py::conv_read_and_update and the taps beside it;
# ::conv_rows_read_and_update where a slot's rows lie end to end: a decode
# step shifts the layer's slab there, elementwise)
CONV_OUT = "conv.out"        # the gate C * v and the output projection
RET_STATE = "ret.state"      # a retention layer's per-slot state: read,
# decay, update, write-back, read-out and normaliser (ops/retention.py::step;
# in a chunk the carried state's read-out and the state's update)
RET_INTRA = "ret.intra"      # a chunk's in-chunk scores under the decay and
# their product with the values
SSM_IN = "ssm.in"            # a Mamba-2 mixer's input projection: the gate z,
# the convolution's input [x; B; C] and the step size's pre-activation
SSM_STATE = "ssm.state"      # its per-slot state: the step size and the decay,
# read, update, write-back, read-out and D (ops/ssd.py::step; in a chunk the
# carried state's read-out and the state's update)
SSM_INTRA = "ssm.intra"      # a chunk's in-block scores C . B under the decay
# and their product with dt x
SSM_OUT = "ssm.out"          # the gated norm and the output projection
ATTN_ABSORB = "attn.absorb"  # latent attention, a decode step: q_nope
# through W_UK^T into the latent's space ahead of the kernel, its read-out
# through W_UV after it
ATTN_EXPAND = "attn.expand"  # latent attention, a chunk: what of the
# latents' way through W_UKV to keys and values runs outside the kernel
ATTN_INDEX = "attn.index"    # a learned index over the kept rows: its
# projections, norm and rotation, the scores of a query against the live
# index keys (the write of the token's own key is KV_WRITE's)
ATTN_SELECT = "attn.select"  # the set a query attends: the top-k or the
# threshold, and positions turned into rows of the pool (or into a mask)
LM_HEAD = "lm_head"          # final norm and logits
SAMPLE = "sample"            # RNG split and ops/sampling.py::sample

ALL = (
    EMBED, LAYERS, NORM, ATTN_QKV, KV_WRITE, KV_GATHER, ATTN_CORE, ATTN_OUT,
    MLP, MOE_ROUTER, MOE_EXPERTS, LM_HEAD, SAMPLE,
)
# What one family's block adds to the thirteen every block opens (the
# benchmark lists them in that family's file, benchmarks/families/):
# EXTRA models/exaone_moe.py's, CONV models/lfm2_moe.py's, RET
# models/brumby.py's, LATENT models/deepseek_v3.py's (beside MOE_SHARED),
# INDEXED what that block adds where its configuration has an indexer, SSM
# models/granitemoehybrid.py's (beside CONV_STATE).
EXTRA = (MOE_SHARED, KV_RING, ATTN_WINDOW)
CONV = (CONV_IN, CONV_STATE, CONV_OUT)
RET = (RET_STATE, RET_INTRA)
LATENT = (ATTN_ABSORB, ATTN_EXPAND)
INDEXED = (ATTN_INDEX, ATTN_SELECT)
SSM = (SSM_IN, SSM_STATE, SSM_INTRA, SSM_OUT)
EVERY = ALL + EXTRA + CONV + RET + LATENT + INDEXED + SSM
