"""`conv_chunks_resumed_share` for granite-4.0-h-micro.rag: the share of the
window's prefill chunks that began at an offset above 0 (`Engine.stats`
`conv_chunks_resumed_sum` / `conv_chunks_sum`), so from the three
convolution rows and the state-space state the chunk before left in the
slot, which travel together (`models/granitemoehybrid.py::_mixer`), and not
from zero."""
from benchmarks.harness import manifest


def read(run):
    return manifest.layer_reader("conv_chunks_resumed_share")(run)
