"""FLOPs latent attention needs in a full prefill chunk (the family's
`latent_chunk_flops` at max_prefill_len queries whose last sees the
window's mean chunk context, Engine.stats deltas `chunk_ctx_tokens_sum` /
`chunk_count`: the expanded form's scores and values a visible pair and
head, and the chunk's own latents through W_UKV once) over what the chips
could do in `chunk_latent_attn_ms`. A program that keeps latents alone
expands the earlier context again in every chunk: that is work above the
need and reads as a lower share. Nothing where the family has no such
count, the program no such counter or the trace no such region.

As `chunk_latent_attn_ms` (its docstring): `moves` names `itl_p50_ms`,
which the chunk does not move."""
from benchmarks.harness import counts, manifest, peaks


def read(run):
    flops = counts.of(run, "latent_chunk_flops")
    if run["rehearse"] or flops is None:
        return None
    st = run["counters"]["stats"]
    chunks = st.get("chunk_count", 0)
    total = st.get("chunk_ctx_tokens_sum", 0)
    ms = manifest.layer_reader("chunk_latent_attn_ms")(run)
    if not chunks or not total or not ms:
        return None
    queries = int(run["mix"]["engine"]["max_prefill_len"])
    context = max(total / chunks, queries)
    peak, _ = peaks.peak_for(run["device"]["kind"])
    return 100.0 * flops(run["config"], queries, context) / (
        ms * 1e-3 * peak * run["chips"])
