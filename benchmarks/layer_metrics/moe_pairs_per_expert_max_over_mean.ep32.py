"""`moe_pairs_per_expert_max_over_mean` for a configuration that holds 8
of 256 routed experts in 13 sparse layers (dots-vlm1-inst-ep32-l16): the
most token-expert pairs one held expert of one sparse layer received in a
decode step over the mean a held expert received. Twelve slots send a held
expert 0.4 pairs a step, so it reads well above 1; it describes the
routing and costs nothing while a decode step streams every held expert.
Nothing where the program keeps no such counter."""
from benchmarks.harness import manifest


def read(run):
    return manifest.layer_reader("moe_pairs_per_expert_max_over_mean")(run)
