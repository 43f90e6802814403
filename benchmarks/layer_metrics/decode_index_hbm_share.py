"""Bytes the index of one decode step must read (the family's
`index_decode_bytes`: every layer's index key of each live token, once, at
the stated type of the pool, and the indexer's weights once) over what the
chips could move in the region `attn.index` of jit_decode. The live tokens
are the mean of Engine.stats' `decode_ctx_tokens_sum` a step over the
window. Nothing where the family has no such count, the program no such
counter or the trace no such region."""
from benchmarks.harness import counts, peaks
from benchmarks.harness import trace_scopes as TS


def read(run):
    need = counts.of(run, "index_decode_bytes")
    if run["rehearse"] or need is None:
        return None
    st = run["counters"]["stats"]
    steps = st.get("decode_steps", 0)
    tokens = st.get("decode_ctx_tokens_sum", 0) / steps if steps else 0
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if not tokens or p is None or "attn.index" not in p["scopes"]:
        return None
    ms = TS.scope_ms(TS.of_run(run), TS.DECODE, ("attn.index",))
    if not ms:
        return None
    kv = counts.KV_ITEMSIZE[run["config"]["precision"]["kv_cache"]]
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * need(run["config"], tokens, kv) / (
        ms * 1e-3 * bw * run["chips"])
