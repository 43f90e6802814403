"""Share of the window's token-expert pairs (decode steps and prefill
chunks, real tokens only) whose chosen expert this program holds:
Engine.stats deltas `moe_pairs_held` / `moe_pairs_all`. With 16 of 128
experts held and an unbiased router it reads 12.5 %; the rest is the work
of the ranks this chip runs without. Nothing where the program keeps no
such counter."""


def read(run):
    st = run["counters"]["stats"]
    every = st.get("moe_pairs_all", 0)
    return 100.0 * st.get("moe_pairs_held", 0) / every if every else None
