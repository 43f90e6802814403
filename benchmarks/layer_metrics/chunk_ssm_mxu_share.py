"""FLOPs the state-space recurrence needs in a full prefill chunk (the
family's `chunk_ssm_flops` at max_prefill_len tokens: the carried state's
read-out, the state's update and the in-chunk part within blocks of the
published `mamba_chunk_size`, from the equations) over what the chips could
do in `chunk_ssm_ms`. Nothing where the family has no such count or the
trace no such region.

As `chunk_ssm_ms` (its docstring): `moves` names `itl_p50_ms`, which the
chunk does not move; no judged metric of `granite-4.0-h-micro.rag` follows
the chunked scan until ROADMAP.md R-B 0a lists the cell under
`ttft_p50_ms`, and that PR points `moves` there."""
from benchmarks.harness import counts, manifest, peaks


def read(run):
    flops = counts.of(run, "chunk_ssm_flops")
    if run["rehearse"] or flops is None:
        return None
    ms = manifest.layer_reader("chunk_ssm_ms")(run)
    if not ms:
        return None
    chunk = int(run["mix"]["engine"]["max_prefill_len"])
    peak, _ = peaks.peak_for(run["device"]["kind"])
    return 100.0 * flops(run["config"], chunk) / (
        ms * 1e-3 * peak * run["chips"])
