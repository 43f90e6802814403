"""Share of the held experts that at least one real token of a decode step
chose, over the window's decode steps: the Engine.stats delta
`moe_decode_experts_touched` (counted on the device in `moe.router`, summed
over sparse layers, returned with the step's outputs) over `moe_decode_steps`
x sparse layers x held experts (from the family's `dims`). It is what a
decode step that groups its pairs by expert would have to stream; today's
step multiplies every token by every held expert (models/hybrid.py::
experts_every) and streams them all, so the reading is no cost yet.
Nothing where the program keeps no such counter."""


def read(run):
    st = run["counters"]["stats"]
    steps = st.get("moe_decode_steps", 0)
    dims = getattr(run.get("family"), "dims", None)
    if not steps or dims is None or "moe_decode_experts_touched" not in st:
        return None
    s = dims(run["config"])
    cells = s.get("Ls", 0) * s.get("Eh", 0)
    if not cells:
        return None
    return 100.0 * st["moe_decode_experts_touched"] / (steps * cells)
