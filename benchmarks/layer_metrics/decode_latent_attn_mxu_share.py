"""FLOPs the latent attention of one decode step needs (the family's
`latent_decode_flops`, the absorbed form: a head and live token, rkv + dr
multiply-adds of score and rkv of read-out, every layer) over what the
chips could do in the region `attn.core` of jit_decode
(ops/latent_attention.py::latent_decode_attention, or whatever computes it
later). The live tokens are the window's mean over decode steps:
Engine.stats deltas `decode_ctx_tokens_sum` / `decode_steps`. The kernel
sits at the chip's ridge (242 FLOPs a byte at the published widths against
240), so `decode_latent_attn_hbm_share` reads the other roof over the same
time; neither may pass 100 %. Nothing where the family has no such count,
the program no such counter or the trace no such region."""
from benchmarks.harness import counts, peaks
from benchmarks.harness import trace_scopes as TS


def read(run):
    flops = counts.of(run, "latent_decode_flops")
    if run["rehearse"] or flops is None:
        return None
    st = run["counters"]["stats"]
    steps = st.get("decode_steps", 0)
    tokens = st.get("decode_ctx_tokens_sum", 0) / steps if steps else 0
    ms = TS.scope_ms(TS.of_run(run), TS.DECODE, ("attn.core",))
    if not tokens or not ms:
        return None
    peak, _ = peaks.peak_for(run["device"]["kind"])
    return 100.0 * flops(run["config"], tokens) / (
        ms * 1e-3 * peak * run["chips"])
