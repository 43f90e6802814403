"""Share of the block table's pages that a decode step's attention has to
read, over the scheduler's decoding iterations in the window: the ratio of
the window's deltas of Engine.stats["decode_kv_pages_read_sum"] (positions
// page_size + 1 a decoding slot, one page an idle row) and
["decode_kv_pages_table_sum"] (max_batch x max_pages). A gather of every
table position reads 100 % of the table by construction; attention that
follows each row's own length (ops/paged_attention.py) reads this share.
It follows the step's length (a shorter step holds fewer slots at one
arrival rate), so a rehearsal on the CPU, whose steps say nothing of the
chip's, reports nothing under this name, as every `decode_` metric; nor
does a program without the counters."""


def read(run):
    st = run["counters"]["stats"]
    table = st.get("decode_kv_pages_table_sum", 0)
    if run["rehearse"] or not table:
        return None
    return 100.0 * st.get("decode_kv_pages_read_sum", 0) / table
