"""Bytes the convolution layers must move in one decode step (the family's
`decode_conv_bytes`: the two projections and the taps of every convolution
layer once, the active slots' state rows read and written, the activations
between the regions) over what the chips could move in `decode_conv_ms`:
the operator's share of its roofline, bounded by memory bandwidth, over the
three regions together, so that a fusion the compiler moves from one region
to its neighbour cannot empty the divisor. Nothing where the family has no
such count or the trace none of the regions."""
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
    conv_bytes = counts.of(run, "decode_conv_bytes")
    if run["rehearse"] or conv_bytes is None:
        return None
    ms = manifest.layer_reader("decode_conv_ms")(run)
    active = _active_mid_trace(run)
    if not ms or not active:
        return None
    kv = counts.KV_ITEMSIZE[run["config"]["precision"]["kv_cache"]]
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * conv_bytes(run["config"], active, kv) / (
        ms * 1e-3 * bw * run["chips"])
