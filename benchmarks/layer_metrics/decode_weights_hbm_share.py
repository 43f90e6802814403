"""Bytes of the weights one decode step's matmul regions must read
(harness/counts.py::weight_bytes, each once: every leaf but the embedding
table and the blocks' norms; of Mixtral's experts only as many as the
active slots can route to) over what the chips could stream in
`decode_matmul_ms`. Bounded by memory bandwidth; 100 % is a matmul phase
that does nothing but stream weights at the peak."""
from benchmarks.harness import counts, manifest, peaks
from benchmarks.harness.weights import model_dims


def _active_mid_trace(run) -> int:
    """Requests decoding at the middle of the traced window."""
    a, b = run["traced"]
    mid = (a + b) / 2
    return sum(1 for r in run["records"]
               if r.first is not None and r.first <= mid
               and not (r.done is not None and r.done <= mid))


def read(run):
    if run["rehearse"]:
        return None
    ms = manifest.layer_reader("decode_matmul_ms")(run)
    active = _active_mid_trace(run)
    if not ms or not active:
        return None
    s = model_dims(run["config"])
    need = 0.0
    for name, b in counts.weight_bytes(run["config"]).items():
        leaf = name.split("/")[-1]
        if leaf in ("tok_embed", "attn_norm", "mlp_norm"):
            continue
        if s["E"] and leaf in ("w_gate", "w_up", "w_down"):
            b = b * min(s["E"], active * s["K"]) / s["E"]
        need += b
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * need / (ms * 1e-3 * bw * run["chips"])
