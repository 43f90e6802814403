"""Device time inside all-reduce / all-gather / reduce-scatter /
all-to-all operations over device busy time, from the trace. Only a cell
across chips has any."""


def read(run):
    t = run["trace"]
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
