"""Bytes of the weights one decode step's matmul regions must read (the
family's `decode_matmul_weight_bytes`, each once: for the llama family
every leaf but the embedding table and the blocks' norms; of Mixtral's
experts only as many as the active slots can route to) over what the chips
could stream in `decode_matmul_ms`. Bounded by memory bandwidth; 100 % is a
matmul phase that does nothing but stream weights at the peak."""
from benchmarks.harness import counts, manifest, peaks


def _active_mid_trace(run) -> int:
    """Requests decoding at the middle of the traced window."""
    a, b = run["traced"]
    mid = (a + b) / 2
    return sum(1 for r in run["records"]
               if r.first is not None and r.first <= mid
               and not (r.done is not None and r.done <= mid))


def read(run):
    weight_bytes = counts.of(run, "decode_matmul_weight_bytes")
    if run["rehearse"] or weight_bytes is None:
        return None
    ms = manifest.layer_reader("decode_matmul_ms")(run)
    active = _active_mid_trace(run)
    if not ms or not active:
        return None
    need = weight_bytes(run["config"], active)
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * need / (ms * 1e-3 * bw * run["chips"])
