"""The run's family's own count of a decode step's bytes, for two slots
at contexts 10 and 20 (a count made on the CPU: no device number)."""
from benchmarks.harness import counts


def read(run):
    step_bytes = counts.of(run, "decode_step_bytes")
    return None if step_bytes is None else step_bytes(run["config"], [10, 20], 2)
