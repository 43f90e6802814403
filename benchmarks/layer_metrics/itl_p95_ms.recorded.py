"""95th percentile of the gaps between output tokens: the stutter a user
sees when another request's prefill is boarded. Recorded, never judged: a
tenth to a fifth of the gaps are stalled by a boarded prompt, by one
prefill program or by several, and the 95th percentile sits at the edge
between those plateaus, so it jumps between them from run to run (PERF.md
section 2)."""


def read(run):
    return run["e2e"].get("itl_p95_ms")
