"""Metric arithmetic on synthetic timestamps: percentiles, window edges,
the tok_per_s credit rule, outcomes."""
from types import SimpleNamespace as NS

import pytest

from benchmarks.harness import metrics as X
from benchmarks.harness.traffic import Planned, Record, Sink


def rec(submit, ts, prompt_len=100, due=None, done=None, out=None,
        reason="length", refused=False):
    s = Sink()
    s.ts = list(ts)
    s.ids = [1] * len(ts)
    s.done_ts = done
    p = Planned(index=0, client=-1, due_s=0.0, prompt_len=prompt_len,
                output_len=out if out is not None else len(ts))
    r = Record(planned=p, prompt=[], sink=s)
    r.submit, r.due, r.refused = submit, submit if due is None else due, refused
    r.request = NS(finish_reason=reason)
    return r


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(101)), 95, 95.0),
    ([7], 99, 7.0),
    ([], 50, None),
])
def test_percentile(values, q, want):
    assert X.percentile(values, q) == want


def test_ttft_counts_by_first_token_time_and_from_due_time():
    rs = [
        rec(9.0, [9.5, 9.6], due=8.9),    # first token before the window
        rec(9.8, [10.2, 10.3], due=9.7),  # due before, first token inside
        rec(19.5, [20.1], due=19.5),      # first token after the window
        rec(15.0, [], due=15.0),          # no token yet
    ]
    assert X.ttfts(rs, 10.0, 20.0) == [pytest.approx(0.5)]


def test_gaps_count_by_the_later_token():
    r = rec(0.0, [9.9, 10.0, 10.4, 19.9, 20.0])
    got = X.gaps([r], 10.0, 20.0)
    assert got == [pytest.approx(0.1), pytest.approx(0.4), pytest.approx(9.5)]


@pytest.mark.parametrize("submit,first,want_prompt", [
    (11.0, 12.0, 100.0),   # wholly inside
    (9.0, 11.0, 50.0),     # half of the prefill inside the window
    (19.5, 20.5, 50.0),    # straddles the close
    (5.0, 9.0, 0.0),       # before the window
    (21.0, 22.0, 0.0),     # after it
    (0.0, 40.0, 25.0),     # the window is a quarter of a very long prefill
])
def test_tokens_served_prompt_credit(submit, first, want_prompt):
    r = rec(submit, [first], prompt_len=100)
    out_tokens = 1.0 if 10.0 <= first < 20.0 else 0.0
    assert X.tokens_served([r], 10.0, 20.0) == pytest.approx(
        want_prompt + out_tokens)


def test_tokens_served_no_first_token_no_credit():
    assert X.tokens_served([rec(11.0, [], prompt_len=4000)], 10.0, 20.0) == 0.0


def test_end_to_end_rate_is_over_the_whole_window():
    r = rec(10.0, [11.0 + 0.1 * i for i in range(50)], prompt_len=450)
    e = X.end_to_end([r], 10.0, 20.0)
    assert e["tok_per_s"] == pytest.approx((450 + 50) / 10.0)
    assert e["itl_p50_ms"] == pytest.approx(100.0)
    assert e["ttft_p50_ms"] == pytest.approx(1000.0)
    assert e["_samples"] == {"ttft": 1, "itl": 49}


def test_lateness_of_requests_due_in_the_window():
    rs = [rec(10.003, [], due=10.0), rec(30.0, [], due=29.9)]
    assert X.lateness(rs, 10.0, 20.0) == [pytest.approx(0.003)]


def test_concurrency_peak():
    rs = [rec(1.0, [2.0], done=12.0), rec(11.0, [11.5], done=13.0),
          rec(11.5, [12.0], done=None), rec(14.0, [14.1], done=15.0)]
    assert X.concurrency_peak(rs, 10.0, 20.0) == 3


@pytest.mark.parametrize("kw,want", [
    (dict(done=5.0), (1, 0, 1)),                           # finished whole
    (dict(done=5.0, out=9), (1, 1, 0)),                    # fewer tokens than asked
    (dict(done=5.0, reason="error"), (1, 1, 0)),           # the engine failed it
    (dict(refused=True), (1, 1, 0)),                       # refused at submit
    (dict(done=None), (1, 0, 0)),                          # in flight at the close
])
def test_outcome(kw, want):
    r = rec(1.0, [2.0, 3.0], **kw)
    o = X.outcome([r], 10.0, 60.0)
    assert (o["attempted"], o["failed"], o["finished"]) == want


def test_outcome_never_finishing_is_failed_and_late_submit_not_attempted():
    stuck = rec(1.0, [], done=None)
    late = rec(100.5, [], done=None)
    o = X.outcome([stuck, late], 100.0, 60.0)
    assert (o["attempted"], o["failed"]) == (1, 1)
