"""Bytes of the experts one decode step's sparse layers must read (the
family's `decode_moe_weight_bytes`: of the held experts only as many as the
active slots can route to) over what the chips could stream in the region
`moe.experts` of jit_decode (models/hybrid.py::moe), for a family whose
sparse layer is its routed experts alone, every one of them held.
`decode_moe_experts_hbm_share` reads the same for a family with a shared
expert beside them, over `moe.experts` + `moe.shared`. Bounded by memory
bandwidth; 100 % is an expert phase that does nothing but stream weights
at the peak. Nothing where the family has no such count, the trace no such
region, or the program opens `moe.shared` (the other reader's case)."""
from benchmarks.harness import counts, peaks
from benchmarks.harness import trace_scopes as TS


def _active_mid_trace(run) -> int:
    """Requests decoding at the middle of the traced window."""
    a, b = run["traced"]
    mid = (a + b) / 2
    return sum(1 for r in run["records"]
               if r.first is not None and r.first <= mid
               and not (r.done is not None and r.done <= mid))


def read(run):
    weight_bytes = counts.of(run, "decode_moe_weight_bytes")
    if run["rehearse"] or weight_bytes is None:
        return None
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or "moe.shared" in p["scopes"]:
        return None
    ms = TS.scope_ms(TS.of_run(run), TS.DECODE, ("moe.experts",))
    active = _active_mid_trace(run)
    if not ms or not active:
        return None
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * weight_bytes(run["config"], active) / (
        ms * 1e-3 * bw * run["chips"])
