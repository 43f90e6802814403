"""FLOPs a full prefill chunk needs (the family's `prefill_chunk_flops`:
matmuls, for Mixtral the routed top-k experts only, causal attention
against the real context at the mean offset of the window's prompts, the
output head for one row) over what the chips could do in the chunk's
device time."""
from benchmarks.harness import counts, manifest, peaks


def read(run):
    chunk_flops = counts.of(run, "prefill_chunk_flops")
    if run["rehearse"] or chunk_flops is None:
        return None
    ms = manifest.layer_reader("prefill_chunk_ms")(run)
    if ms is None:
        return None
    chunk = int(run["mix"]["engine"]["max_prefill_len"])
    # mean start of a full chunk over the window's prompts
    offs = []
    for r in run["records"]:
        if r.submit is None or not run["w0"] <= r.submit < run["w1"]:
            continue
        for k in range(r.planned.prompt_len // chunk):
            offs.append(k * chunk)
    if not offs:
        return None
    off = sum(offs) / len(offs)
    flops = chunk_flops(run["config"], chunk, int(off))
    peak, _ = peaks.peak_for(run["device"]["kind"])
    return 100.0 * flops / (ms * 1e-3 * peak * run["chips"])
