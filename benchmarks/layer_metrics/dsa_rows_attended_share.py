"""Share of the rows its sequence has kept that a decoding slot's query
attends, over the decode steps of the window: the ratio of the
Engine.stats deltas `dsa_rows_attended_sum` (min(index_topk, context) a
decoding slot and step) and `dsa_rows_live_sum` (the context), counted on
the host once a step, not per layer (serve/engine.py::_count_step). 100 %
means nothing was selected: every context was at most index_topk long.
Nothing where the program keeps no such counter."""


def read(run):
    st = run["counters"]["stats"]
    live = st.get("dsa_rows_live_sum", 0)
    if not live:
        return None
    return 100.0 * st.get("dsa_rows_attended_sum", 0) / live
