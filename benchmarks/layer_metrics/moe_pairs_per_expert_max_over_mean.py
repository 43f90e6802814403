"""How unevenly the decode steps' token-expert pairs fall on the held
experts: the most pairs one held expert of one sparse layer received in a
step, over the mean a held expert received, both summed over the window's
decode steps (Engine.stats deltas `moe_decode_expert_pairs_max_sum` and
`moe_decode_pairs_held`, the latter over sparse layers x held experts,
from the family's `dims`). 1 is an even spread. It describes the routing,
not yet a cost: today's decode step multiplies every token by every held
expert (models/exaone_moe.py::_experts_every), so an uneven spread cannot
move `itl_p50_ms`; only the prefill chunks group pairs by expert, and
their blocks follow the same router. The reading becomes a cost once the
decode step groups too, where a step waits for its fullest expert.
Nothing where the program keeps no such counter."""


def read(run):
    st = run["counters"]["stats"]
    held = st.get("moe_decode_pairs_held", 0)
    dims = getattr(run.get("family"), "dims", None)
    if not held or dims is None:
        return None
    s = dims(run["config"])
    cells = s.get("Ls", 0) * s.get("Eh", 0)
    if not cells:
        return None
    return st.get("moe_decode_expert_pairs_max_sum", 0) / (held / cells)
