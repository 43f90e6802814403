"""A second family, as a test fixture (tests/benchmarks/test_bench_family.py
copies it into a copy of the tree): what a block other than the llama
family's asks of the harness, at the smallest size that shows it.

Its table has two stacks with different leaves (`lead/...`, one layer,
beside the program's `layers/...`), a path three deep, a float32 bias kind,
and a leaf outside any stack. It runs on the program's llama module (the
paged engine serves no other today), which reads its own leaves and passes
the others by; the regions and counts are this file's own.
"""
from benchmarks.families import llama_family as base
from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

SCOPES = ("attn.window", "moe.shared")
MATMUL_SCOPES = base.MATMUL_SCOPES + ("moe.shared",)

dims = base.dims
program = base.program


def leaf_table(cfg):
    s = dims(cfg)
    t = dict(base.leaf_table(cfg))
    t["lead/in_norm"] = Leaf((1, s["D"]), (), 0, "norm", True)
    t["lead/w_in"] = Leaf((1, s["D"], 2 * s["D"]), (1,), s["D"], "int8", True)
    t["lead/gate/bias"] = Leaf((1, 8), (), 0, "bias", True)
    t["route_bias"] = Leaf((8,), (), 0, "bias")
    return t


def decode_step_bytes(cfg, ctx_lens, kv_itemsize=2):
    """The llama block's bytes and every leaf of the leading stack once."""
    lead = sum(b for path, b in weight_bytes(leaf_table(cfg)).items()
               if path.startswith("lead/"))
    return base.decode_step_bytes(cfg, ctx_lens, kv_itemsize) + lead


# decode_matmul_weight_bytes and prefill_chunk_flops are not defined: their
# readers return nothing for a cell of this family.
