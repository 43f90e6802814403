"""`decode_moe_experts_hbm_share` for a configuration that holds a 32nd of
the routed experts (8 of 256 beside the shared one,
dots-vlm1-inst-ep32-l16): bytes of the held experts and the shared expert
that one decode step's sparse layers must read (the family's
`decode_moe_weight_bytes`) over what the chips could stream in the regions
`moe.experts` and `moe.shared` of jit_decode (models/hybrid.py::moe). The
same reader; a name of its own because the share of experts held differs
from the cells' that report the other."""
from benchmarks.harness import manifest


def read(run):
    return manifest.layer_reader("decode_moe_experts_hbm_share")(run)
