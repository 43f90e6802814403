"""What every JAX entry point does once, before its first jit.

* The persistent compilation cache gets a place. A fresh machine compiles
  every bucket, chunk and decode executable of the engine; a warm cache
  turns that start-up into reads. Where JAX_COMPILATION_CACHE_DIR is set,
  JAX reads it itself and this code sets no directory. Otherwise, on an
  accelerator, the cache lives in one fixed, git-ignored directory of the
  checkout: the path is part of the cache key, so a directory made from
  tempfile, a pid or the time would never hit. Metadata (region names,
  source lines) is part of the key too, so that a profiler capture names
  the code that runs and not an older build's. The CPU backend gets no
  default cache: nobody deploys it, and jaxlib 0.9.0 logs ~6 KB of
  machine-feature warnings to stderr for every XLA:CPU executable it
  reloads, which fills the pipe of any parent that does not drain it.
* One line names the device the process really runs on. JAX falls back to
  the CPU when it finds no accelerator, and a server that came up there
  looks healthy; the line is what chip_smoke.py (and an operator) reads.
* Compilations are counted on the shared metrics registry, so /metrics
  (and a benchmark window) can say "no executable was built after the
  warm-up", and how many seconds of start-up were compilation.
* Device memory, which only the process that holds the chip can read, is
  offered as gauges (the server's /metrics) and as one line (the trainer).
"""
from __future__ import annotations

import json
import os
from typing import Optional

from substratus_tpu.observability.metrics import METRICS

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
DEVICE_LINE_PREFIX = "jax devices: "
MEMORY_LINE_PREFIX = "jax device memory: "

# jax.monitoring event names (jax/_src/dispatch.py, compiler.py). The first
# wraps compile_or_get_cached: it fires once for every executable built,
# whether the compiler ran or the persistent cache answered.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

METRICS.describe(
    "substratus_jax_compilations_total",
    "Executables built since start-up (a new shape or program; includes "
    "loads from the persistent compilation cache).", type="counter",
)
METRICS.describe(
    "substratus_jax_compile_seconds_total",
    "Seconds spent building those executables (compiler or cache read).",
    type="counter",
)
METRICS.describe(
    "substratus_jax_compile_cache_hits_total",
    "Executables the persistent compilation cache answered.",
    type="counter",
)
for _name, _help in (
    ("substratus_device_bytes_in_use", "Device memory in use now."),
    ("substratus_device_peak_bytes_in_use",
     "Most device memory in use at any time since start-up."),
    ("substratus_device_bytes_limit", "Device memory the process may use."),
):
    METRICS.describe(_name, _help + " By local device id; absent where "
                     "the backend reports no memory_stats (the CPU).",
                     type="gauge")
_listening = False


def count_compilations() -> None:
    """Feed the three counters from jax.monitoring (idempotent)."""
    global _listening
    if _listening:
        return
    import jax.monitoring

    def on_duration(event: str, seconds: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            METRICS.inc("substratus_jax_compilations_total")
            METRICS.inc("substratus_jax_compile_seconds_total", by=seconds)

    def on_event(event: str, **kwargs) -> None:
        if event == _CACHE_HIT_EVENT:
            METRICS.inc("substratus_jax_compile_cache_hits_total")

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _listening = True


def configure_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache; returns the directory in
    use (None: the CPU backend with no directory given from outside). The
    size and compile-time floors go, so the engine's small executables
    (sampling, page import/export) are cached too. Initialises the
    backend, so it comes after jax.distributed.initialize."""
    import jax

    cache_dir = os.environ.get(CACHE_ENV)
    if not cache_dir and jax.default_backend() != "cpu":
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # An executable keeps the metadata it was compiled with: the region
    # names (ops/scopes.py) and source lines a profiler capture shows. By
    # default the key leaves metadata out, so an entry written by an older
    # build with the same arithmetic would be loaded and the capture would
    # show that build's names (seen on the chip, PR 24: first_sample came
    # back without its `sample` region). With it in the key a capture
    # names the code that runs; the price is a recompile after an edit
    # that only moves lines.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_dir


def device_summary() -> dict:
    """The device as JAX reports it (initialises the backend)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def jax_startup() -> dict:
    """configure_compile_cache() and count_compilations(), then the one
    device line on stdout (DEVICE_LINE_PREFIX + a JSON object). Returns
    the device summary."""
    cache_dir = configure_compile_cache()
    count_compilations()
    summary = device_summary()
    print(
        DEVICE_LINE_PREFIX
        + json.dumps({**summary, "compile_cache": cache_dir}),
        flush=True,
    )
    return summary


def device_memory() -> list:
    """memory_stats() of each local device that reports them, as
    [{"device", "bytes_in_use", "peak_bytes_in_use", "bytes_limit"}]; also
    sets the substratus_device_* gauges. The CPU backend reports none."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            continue
        row = {"device": d.id}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            row[key] = int(stats.get(key, 0))
            METRICS.set(f"substratus_device_{key}", row[key],
                        {"device": str(d.id)})
        out.append(row)
    return out


def print_device_memory() -> None:
    """The one memory line on stdout (MEMORY_LINE_PREFIX + a JSON list)."""
    print(MEMORY_LINE_PREFIX + json.dumps(device_memory()), flush=True)
