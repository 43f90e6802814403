"""`prefill_attn_ms` where it moves tokens per second and not a judged
time to first token. The same reading as prefill_attn_ms.py."""
from benchmarks.harness.manifest import layer_reader


def read(run):
    return layer_reader("prefill_attn_ms")(run)
