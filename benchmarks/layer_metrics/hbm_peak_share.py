"""Peak device memory over the device's limit, on the fullest chip
(memory_stats() after the window, before the reference runs)."""


def read(run):
    best = None
    for m in run["memory"]:
        if m.get("bytes_limit"):
            s = 100.0 * m.get("peak_bytes_in_use", 0) / m["bytes_limit"]
            best = s if best is None else max(best, s)
    return best
