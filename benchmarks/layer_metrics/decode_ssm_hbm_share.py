"""Bytes the state-space state's step must move in one decode step (the
family's `decode_ssm_bytes`: `S` of the slots decoding in every Mamba layer
once read and once written at the stated type, and x, B, C, dt and the
output of those rows) over what the chips could move in `decode_ssm_ms`:
the state step's share of its roofline, bounded by memory bandwidth. A step
that moves every slot's state, live or idle, reads at most the share of
slots that decode (`decode_state_rows_live_share.granite`). Nothing where
the family has no such count or the trace no such region."""
from benchmarks.harness import counts, manifest, peaks


def _active_mid_trace(run) -> int:
    """Requests decoding at the middle of the traced window (counted as
    decode_weights_hbm_share counts them)."""
    a, b = run["traced"]
    mid = (a + b) / 2
    return sum(1 for r in run["records"]
               if r.first is not None and r.first <= mid
               and not (r.done is not None and r.done <= mid))


def read(run):
    state_bytes = counts.of(run, "decode_ssm_bytes")
    if run["rehearse"] or state_bytes is None:
        return None
    ms = manifest.layer_reader("decode_ssm_ms")(run)
    active = _active_mid_trace(run)
    if not ms or not active:
        return None
    act = counts.KV_ITEMSIZE[run["config"]["precision"]["activations"]]
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * state_bytes(run["config"], active, act) / (
        ms * 1e-3 * bw * run["chips"])
