"""`prefill_chunk_ms` where it moves tokens per second and not a judged
time to first token (the contract splits a quantity whose cells report
different end-to-end metrics). The same reading as prefill_chunk_ms.py."""
from benchmarks.harness.manifest import layer_reader


def read(run):
    return layer_reader("prefill_chunk_ms")(run)
