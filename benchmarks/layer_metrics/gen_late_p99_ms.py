"""How late the load generator ran: submit time minus due time, 99th
percentile over requests due in the window (harness clock). A starved
generator must not be read as a fast server."""
from benchmarks.harness import metrics


def read(run):
    late = metrics.lateness(run["records"], run["w0"], run["w1"])
    p = metrics.percentile(late, 99)
    return None if p is None else p * 1e3
