"""Bytes the attention of one decode step under a learned index must read
(the family's `sparse_decode_bytes`: every layer's latent row of the min(k,
context) tokens each slot's query attends, once, at the stated type of the
pool) over what the chips could move in the region `attn.core` of
jit_decode: the roofline share of whatever reads the picked rows and
attends over them. The contexts are those of the requests decoding at the
middle of the traced window, as `decode_hbm_share` counts them. Nothing
where the family has no such count or the trace no such region."""
from benchmarks.harness import counts, peaks
from benchmarks.harness import trace_scopes as TS


def read(run):
    need = counts.of(run, "sparse_decode_bytes")
    if run["rehearse"] or need is None or not run["traced"][0]:
        return None
    a, b = run["traced"]
    mid = (a + b) / 2
    ctx = [r.planned.prompt_len + sum(1 for ts in r.sink.ts if ts <= mid)
           for r in run["records"]
           if r.first is not None and r.first <= mid
           and not (r.done is not None and r.done <= mid)]
    ms = TS.scope_ms(TS.of_run(run), TS.DECODE, ("attn.core",))
    if not ctx or not ms:
        return None
    kv = counts.KV_ITEMSIZE[run["config"]["precision"]["kv_cache"]]
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * need(run["config"], ctx, kv) / (
        ms * 1e-3 * bw * run["chips"])
