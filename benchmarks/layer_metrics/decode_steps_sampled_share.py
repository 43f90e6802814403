"""Share of the window's decode steps (and verify rounds) whose batch held a
row with a temperature above 0, so whose sampler took the branch that sorts
the vocabulary and draws (ops/sampling.py::sample) and not the argmax alone:
the ratio of the Engine.stats deltas `decode_steps_sampled` and
`decode_steps`, counted on the host per dispatch from the temperatures handed
to the program in the same call (serve/engine.py::_count_step). It follows
the traffic: 0 where every request is greedy. Which steps fall in the window
follows the chip's pace, so a rehearsal on the CPU reports nothing under this
name, as every `decode_` metric; nor does a program without the counters."""


def read(run):
    st = run["counters"]["stats"]
    steps = st.get("decode_steps", 0)
    if run["rehearse"] or not steps:
        return None
    return 100.0 * st.get("decode_steps_sampled", 0) / steps
