"""Executables built inside the measured window
(substratus_jax_compilations_total, which also counts loads from the
persistent cache); every shape is warmed before it, so this reads 0."""


def read(run):
    return float(run["counters"]["substratus_jax_compilations_total"])
