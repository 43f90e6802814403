"""Device time of one execution of the prefill-chunk program at the
largest bucket (max_prefill_len tokens), median over the traced window.
The trace names a compiled program by its jitted function and an id, one
id a bucket; the program of the full chunk is the one whose executions
take longest."""


def read(run):
    t = run["trace"]
    progs = {k: v for k, v in (t or {}).get("programs", {}).items()
             if "chunk_prefill" in k}
    if not progs:
        return None
    return max(v["median_s"] for v in progs.values()) * 1e3
