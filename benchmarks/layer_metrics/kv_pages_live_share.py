"""Mean share of the KV pool's pages that an active slot references, over
the scheduler's decoding iterations in the window: the ratio of the
window's deltas of Engine.stats["kv_live_pages_sum"] and
["kv_pool_pages_sum"] (pages the prefix registry alone still holds are
not live)."""


def read(run):
    st = run["counters"]["stats"]
    pool = st.get("kv_pool_pages_sum", 0)
    return 100.0 * st.get("kv_live_pages_sum", 0) / pool if pool else None
