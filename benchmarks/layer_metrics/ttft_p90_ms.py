"""90th percentile of time to first token in the window (harness clock).
Recorded, never judged: with about a hundred requests a window it has ten
samples beyond it."""
from benchmarks.harness import metrics


def read(run):
    p = metrics.percentile(metrics.ttfts(run["records"], run["w0"], run["w1"]), 90)
    return None if p is None else p * 1e3
