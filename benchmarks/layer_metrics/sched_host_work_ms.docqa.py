"""`sched_host_work_ms` for dots-vlm1.docqa: host work of one scheduler
iteration that launched a decode step (the span `engine.iter` minus its
`engine.wait.*` and `engine.idle` descendants, median over the traced
window), here with twelve slots' block tables of 896 pages handed over a
step. Hidden under the device's step while it is shorter than the step."""
from benchmarks.harness import manifest


def read(run):
    return manifest.layer_reader("sched_host_work_ms")(run)
