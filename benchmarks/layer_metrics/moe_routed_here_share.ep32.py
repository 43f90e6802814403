"""`moe_routed_here_share` for a configuration that holds 8 of 256 routed
experts under a group limit (dots-vlm1-inst-ep32-l16): the share of the
window's token-expert pairs (decode steps and prefill chunks, real tokens
only) whose chosen expert this program holds, Engine.stats deltas
`moe_pairs_held` / `moe_pairs_all`. With an unbiased router the group
limit is symmetric over groups and over a group's experts, so it reads
8 / 256 = 3.125 %; a limit that favoured or starved group 0 would show
here. Nothing where the program keeps no such counter."""
from benchmarks.harness import manifest


def read(run):
    return manifest.layer_reader("moe_routed_here_share")(run)
