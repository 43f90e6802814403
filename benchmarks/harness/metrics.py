"""End-to-end metric arithmetic over the sinks' timestamps.

All of it is the harness's own: the program's histograms are read only by
per-layer metrics. A window is [w0, w1); a reading counts by the timestamp
that falls inside it, so requests in flight at either edge count only for
the part inside.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile; None when there is no sample."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(records: Iterable[Any], w0: float, w1: float) -> List[float]:
    """First-token time minus the time the request was due, in seconds, for
    requests whose first token falls in the window."""
    out = []
    for r in records:
        f = r.first
        if f is not None and r.due is not None and w0 <= f < w1:
            out.append(f - r.due)
    return out


def gaps(records: Iterable[Any], w0: float, w1: float) -> List[float]:
    """Gaps between consecutive output tokens of one request, for gaps that
    end inside the window."""
    out = []
    for r in records:
        ts = r.sink.ts
        for a, b in zip(ts, ts[1:]):
            if w0 <= b < w1:
                out.append(b - a)
    return out


def tokens_served(records: Iterable[Any], w0: float, w1: float) -> float:
    """Tokens served inside the window. An output token counts at its
    emission. A request's prompt tokens are spread evenly over the interval
    from its submission to its first token, and the part inside the window
    counts, so a window edge costs a fraction of a prompt, not a whole one.
    A request with no first token yet earns no prompt credit."""
    total = 0.0
    for r in records:
        ts = r.sink.ts
        total += sum(1 for t in ts if w0 <= t < w1)
        if r.submit is not None and ts:
            a, b = r.submit, ts[0]
            inside = max(0.0, min(b, w1) - max(a, w0))
            if b > a:
                total += r.planned.prompt_len * inside / (b - a)
            elif w0 <= b < w1:
                total += r.planned.prompt_len
    return total


def lateness(records: Iterable[Any], w0: float, w1: float) -> List[float]:
    """Submit time minus due time of requests due in the window."""
    return [
        r.submit - r.due for r in records
        if r.submit is not None and r.due is not None and w0 <= r.due < w1
    ]


def queue_waits(records: Iterable[Any], w0: float, w1: float,
                wait_of) -> List[float]:
    """The system's own reading of submit -> first prefill (`wait_of`, from
    the system adapter), for requests submitted in the window."""
    waits = (wait_of(r.request) for r in records
             if r.submit is not None and w0 <= r.submit < w1)
    return [w for w in waits if w is not None]


def concurrency_peak(records: Iterable[Any], w0: float, w1: float) -> int:
    """Most requests between submission and completion at once, in the
    window (requests not yet complete count to the window's end)."""
    events = []
    for r in records:
        if r.submit is None or r.submit >= w1:
            continue
        end = r.done if r.done is not None else w1
        if end <= w0:
            continue
        events.append((max(r.submit, w0), 1))
        events.append((min(end, w1), -1))
    events.sort(key=lambda e: (e[0], e[1]))
    peak = cur = 0
    for _, d in events:
        cur += d
        peak = max(peak, cur)
    return peak


def outcome(records: Iterable[Any], t_close: float, deadline_s: float
            ) -> Dict[str, int]:
    """attempted: requests submitted before the close. failed: refused,
    ended in error, ended with another number of tokens than asked, or
    still without an end `deadline_s` after submission."""
    attempted = failed = finished = 0
    for r in records:
        if r.submit is None or r.submit >= t_close:
            continue
        attempted += 1
        reason = getattr(r.request, "finish_reason", None)
        if r.refused or (r.done is not None and reason == "error"):
            failed += 1
        elif r.done is not None and r.done <= t_close:
            if len(r.sink.ids) != r.planned.output_len:
                failed += 1
            else:
                finished += 1
        elif t_close - r.submit > deadline_s:
            failed += 1
    return {"attempted": attempted, "failed": failed, "finished": finished}


def end_to_end(records: List[Any], w0: float, w1: float) -> Dict[str, Any]:
    """Every end-to-end quantity the harness knows, with sample counts. A
    cell reports those that BENCHMARK.json lists for it."""
    tt = ttfts(records, w0, w1)
    gg = gaps(records, w0, w1)
    ms = lambda v: None if v is None else v * 1e3  # noqa: E731
    return {
        "ttft_p50_ms": ms(percentile(tt, 50)),
        "itl_p50_ms": ms(percentile(gg, 50)),
        "itl_p95_ms": ms(percentile(gg, 95)),
        "tok_per_s": tokens_served(records, w0, w1) / (w1 - w0),
        "_samples": {"ttft": len(tt), "itl": len(gg)},
    }
