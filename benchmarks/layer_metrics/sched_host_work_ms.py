"""Host work of one scheduler iteration: the span `engine.iter` minus its
`engine.wait.*` (blocked on the device) and `engine.idle` descendants,
median over the iterations of the traced window that launched a decode
step (they hold an `engine.dispatch`). The spans are opened by
observability/timeline.py::StepTimeline.phase from serve/engine.py."""
from benchmarks.harness import trace_scopes as TS


def read(run):
    return TS.host_work_ms(TS.of_run(run))
