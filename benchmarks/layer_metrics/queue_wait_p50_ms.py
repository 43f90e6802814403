"""Median wait from submit to the request's first prefill, over requests
submitted in the window: the program's own clock reading (the value it
observes into substratus_serve_queue_wait_seconds), taken from each
request's journey so that the histogram's buckets do not round it."""
from benchmarks.harness import metrics


def read(run):
    waits = metrics.queue_waits(run["records"], run["w0"], run["w1"],
                                run["queue_wait_s"])
    p = metrics.percentile(waits, 50)
    return None if p is None else p * 1e3
