"""`decode_moe_experts_hbm_share` for glm-5-ep32-l13, which holds 8 of 256
routed experts of 2,048 beside the shared one over a hidden size of 6,144:
bytes of the held experts and the shared expert that one decode step's
sparse layers must read (the family's `decode_moe_weight_bytes`) over what
the chips could stream in the regions `moe.experts` and `moe.shared` of
jit_decode (models/hybrid.py::moe). The same reader; a name of its own
because the experts' widths and the slots that route to them differ from
the cells' that report the others."""
from benchmarks.harness import manifest


def read(run):
    return manifest.layer_reader("decode_moe_experts_hbm_share")(run)
