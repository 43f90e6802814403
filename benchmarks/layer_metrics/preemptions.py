"""Requests evicted mid-decode in the window (Engine.stats["preemptions"]);
the pools are sized so that this reads 0."""


def read(run):
    return float(run["counters"]["stats"].get("preemptions", 0))
