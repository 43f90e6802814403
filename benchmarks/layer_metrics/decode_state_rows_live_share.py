"""Share of the per-slot state's rows that belong to a decoding slot, over
the scheduler's decoding iterations in the window: the ratio of the
Engine.stats deltas `state_rows_live_sum` (slots decoding) and
`state_rows_sum` (max_batch), counted on the host per iteration
(serve/engine.py::_iterate). It is the share of the state a step had to
move of what a step over every slot moves: what a step that skipped idle
rows would save, and what it would make follow the traffic. It follows the
step's length (a shorter step holds fewer slots at one arrival rate), so a
rehearsal on the CPU reports nothing under this name, as every `decode_`
metric; nor does a program without the counters."""


def read(run):
    st = run["counters"]["stats"]
    rows = st.get("state_rows_sum", 0)
    if run["rehearse"] or not rows:
        return None
    return 100.0 * st.get("state_rows_live_sum", 0) / rows
