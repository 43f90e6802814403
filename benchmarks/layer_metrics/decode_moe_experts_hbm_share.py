"""Bytes of the held experts and the shared expert that one decode step's
sparse layers must read (the family's `decode_moe_weight_bytes`: of the
held experts only as many as the active slots can route to) over what the
chips could stream in the regions `moe.experts` and `moe.shared` of
jit_decode (models/exaone_moe.py::_moe). Bounded by memory bandwidth;
100 % is an expert phase that does nothing but stream weights at the peak.
Nothing where the family has no such count or the trace no such region."""
from benchmarks.harness import counts, peaks
from benchmarks.harness import trace_scopes as TS

SCOPES = ("moe.experts", "moe.shared")


def _active_mid_trace(run) -> int:
    """Requests decoding at the middle of the traced window (counted as
    decode_weights_hbm_share counts them)."""
    a, b = run["traced"]
    mid = (a + b) / 2
    return sum(1 for r in run["records"]
               if r.first is not None and r.first <= mid
               and not (r.done is not None and r.done <= mid))


def read(run):
    weight_bytes = counts.of(run, "decode_moe_weight_bytes")
    if run["rehearse"] or weight_bytes is None:
        return None
    ms = TS.scope_ms(TS.of_run(run), TS.DECODE, SCOPES)
    active = _active_mid_trace(run)
    if not ms or not active:
        return None
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * weight_bytes(run["config"], active) / (
        ms * 1e-3 * bw * run["chips"])
