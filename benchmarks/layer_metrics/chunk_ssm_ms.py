"""Device time the prefill-chunk program at the largest bucket
(max_prefill_len tokens) spends in the state-space recurrence: the regions
`ssm.state` (the step size, the carried state's read-out and the state's
update) and `ssm.intra` (the in-block scores C . B under the decay and
their product with dt x) of ops/ssd.py::chunk, median over the program's
executions in the traced window. Nothing where the program opens neither
region.

`moves` says `itl_p50_ms` because a cell's metric has to move one the cell
reports, and `granite-4.0-h-micro.rag` reports that and `setup_s` alone. It
does not in fact move it: an admission runs all its chunks in one
iteration, so one decoding gap in about fifteen is long and the median gap
is the bare step. No judged metric of the cell follows the chunk until
ROADMAP.md R-B 0a lists the cell under `ttft_p50_ms` and `tok_per_s`; the
same PR points this metric's `moves` at `ttft_p50_ms`."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("ssm.state", "ssm.intra")


def read(run):
    p = TS.named_program(TS.of_run(run), TS.CHUNK)
    if p is None or not any(s in p["scopes"] for s in SCOPES):
        return None
    return TS.scope_ms(TS.of_run(run), TS.CHUNK, SCOPES)
