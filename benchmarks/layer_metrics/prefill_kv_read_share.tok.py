"""Share of its block-table row that a prefill chunk's attention has to
read, over the chunk dispatches in the window: the ratio of the window's
deltas of Engine.stats["prefill_kv_pages_read_sum"] (the chunk's last query
position // page_size + 1) and ["prefill_kv_pages_table_sum"] (max_pages),
both added on the host per chunk dispatch (serve/engine.py::_run_chunks). A
gather of every table position reads 100 % of the row by construction;
attention that follows the row's live context (ops/paged_attention.py::
paged_chunk_attention) reads this share. Which chunks fall in the window
follows the chip's pace, so a rehearsal on the CPU reports nothing under
this name, as every `prefill_` metric; nor does a program without the
counters."""


def read(run):
    st = run["counters"]["stats"]
    table = st.get("prefill_kv_pages_table_sum", 0)
    if run["rehearse"] or not table:
        return None
    return 100.0 * st.get("prefill_kv_pages_read_sum", 0) / table
