"""Median time to first token where it is not judged: a closed loop above
capacity, where it is mostly the wait in the queue and the median of some
twenty requests a window is one of a few queueing patterns (PERF.md
section 2)."""


def read(run):
    return run["e2e"].get("ttft_p50_ms")
