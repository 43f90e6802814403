"""Device time one decode step spends in the region `attn.select`
(ops/kvcache.py::latent_attention: the set each slot's query attends, the
top-k over its index scores, and the positions turned into rows of the
pool), median over the executions of jit_decode in the traced window.
Nothing where the program opens no such region."""
from benchmarks.harness import trace_scopes as TS

SCOPE = "attn.select"


def read(run):
    p = TS.named_program(TS.of_run(run), TS.DECODE)
    if p is None or SCOPE not in p["scopes"]:
        return None
    return TS.scope_ms(TS.of_run(run), TS.DECODE, (SCOPE,))
