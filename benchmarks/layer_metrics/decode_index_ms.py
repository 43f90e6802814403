"""Device time one decode step spends in the region `attn.index`
(models/deepseek_v3.py::index_of and ops/kvcache.py::latent_attention: the
indexer's three projections, the key's norm and the rotations, and the
scores of each slot's query against its live index keys,
ops/sparse_index.py), median over the executions of jit_decode in the
traced window. Nothing where the program opens no such region."""
from benchmarks.harness import trace_scopes as TS

SCOPE = "attn.index"


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or SCOPE not in p["scopes"]:
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, (SCOPE,))
