"""Device time one decode step spends moving the KV pool without reading
it for attention: the region `kv.write` (the scatter of the new rows,
ops/kvcache.py) plus the self time of `layers` (models/llama.py opens it
around the scan over layers: a layer's pool sliced out of and written back
into the stacked pool, compiler-inserted copies of the carry). Median over
the executions of jit_decode in the traced window."""
from benchmarks.harness import trace_scopes as TS


def read(run):
    return TS.scope_ms(TS.of_run(run), TS.DECODE, ("kv.write", "layers"))
