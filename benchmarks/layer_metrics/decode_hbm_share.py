"""Bytes a decode step must move (every weight once, the real context of
the active slots, one new K/V row a slot: the family's
`decode_step_bytes`, from shapes) over what the chips could move in the
step's device time. Bounded by memory bandwidth: decode at these batch
sizes is far below the compute roof."""
from benchmarks.harness import counts, peaks


def read(run):
    t = run["trace"]
    m = (t or {}).get("modules", {}).get("jit_decode")
    step_bytes = counts.of(run, "decode_step_bytes")
    if not m or run["rehearse"] or step_bytes is None:
        return None
    a, b = run["traced"]
    mid = (a + b) / 2
    ctx = []
    for r in run["records"]:
        if r.first is None or r.first > mid:
            continue
        if r.done is not None and r.done <= mid:
            continue
        done = sum(1 for ts in r.sink.ts if ts <= mid)
        ctx.append(r.planned.prompt_len + done)
    if not ctx:
        return None
    kv = counts.KV_ITEMSIZE[run["config"]["precision"]["kv_cache"]]
    need = step_bytes(run["config"], ctx, kv)
    _, bw = peaks.peak_for(run["device"]["kind"])
    return 100.0 * need / (m["median_s"] * bw * run["chips"])
