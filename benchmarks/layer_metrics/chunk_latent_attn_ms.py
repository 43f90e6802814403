"""Device time the prefill-chunk program at the largest bucket
(max_prefill_len tokens) spends in latent attention's expanded form: the
regions `attn.expand` (what of the latents' way through W_UKV to keys and
values runs outside the kernel) and `attn.core`
(ops/latent_attention.py::latent_chunk_attention: a block of pages expanded
in VMEM, scores, softmax, values), median over the program's executions in
the traced window. Nothing where the program opens no `attn.expand`.

`moves` says `itl_p50_ms` because a cell's metric has to move one the cell
reports, and `dots-vlm1.docqa` reports that and `setup_s` alone, as
`chunk_retention_ms` (its docstring). It does not in fact move it: an
admission runs all its chunks in one iteration, so one decoding gap in
about eighty is long and the median gap is the bare step. No judged metric
of the cell follows the chunk until ROADMAP.md R-B 0a lists the cell under
`ttft_p50_ms`; the same PR points this metric's `moves` there."""
from benchmarks.harness import trace_scopes as TS

SCOPES = ("attn.expand", "attn.core")


def read(run):
    p = TS.named_program(TS.of_run(run), TS.CHUNK)
    if p is None or "attn.expand" not in p["scopes"]:
        return None
    return TS.scope_ms(TS.of_run(run), TS.CHUNK, SCOPES)
