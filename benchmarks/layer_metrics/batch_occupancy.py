"""Mean share of decode slots in use, over the scheduler's iterations in
the window (substratus_serve_batch_occupancy_ratio)."""


def read(run):
    m = run["counters"]["substratus_serve_batch_occupancy_ratio"]["mean"]
    return None if m is None else 100.0 * m
