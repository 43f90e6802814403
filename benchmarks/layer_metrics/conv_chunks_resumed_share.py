"""Share of the window's prefill chunks that began at an offset above 0,
so from the convolution rows the chunk before left in the slot's state
(ops/kvcache.py::conv_read_and_update) and not from zeros: the ratio of
the Engine.stats deltas `conv_chunks_resumed_sum` and `conv_chunks_sum`,
counted on the host per chunk dispatch (serve/engine.py::_run_chunks). It
follows the prompts' lengths over the chunk size. Nothing where the program
keeps no such counter."""


def read(run):
    st = run["counters"]["stats"]
    chunks = st.get("conv_chunks_sum", 0)
    return 100.0 * st.get("conv_chunks_resumed_sum", 0) / chunks if chunks else None
