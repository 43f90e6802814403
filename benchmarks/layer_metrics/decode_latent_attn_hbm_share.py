"""Bytes the latent attention of one decode step must read (the family's
`latent_decode_bytes`: every layer's row [ckv; kr] of each live token,
once, at the stated type of the pool; keys and values are the same bytes
and every head shares them) over what the chips could move in the region
`attn.core` of jit_decode. The live tokens and the time are
`decode_latent_attn_mxu_share`'s, which reads the compute roof of the same
kernel. A pool that stores the row wider than the equations have it (640
for 576) reads that much under 100 % at the roof. Nothing where the family
has no such count, the program no such counter or the trace no such
region."""
from benchmarks.harness import counts, peaks
from benchmarks.harness import trace_scopes as TS


def read(run):
    need = counts.of(run, "latent_decode_bytes")
    if run["rehearse"] or need is None:
        return None
    st = run["counters"]["stats"]
    steps = st.get("decode_steps", 0)
    tokens = st.get("decode_ctx_tokens_sum", 0) / steps if steps else 0
    ms = TS.scope_ms(TS.of_run(run), TS.DECODE, ("attn.core",))
    if not tokens or not ms:
        return None
    kv = counts.KV_ITEMSIZE[run["config"]["precision"]["kv_cache"]]
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * need(run["config"], tokens, kv) / (
        ms * 1e-3 * bw * run["chips"])
