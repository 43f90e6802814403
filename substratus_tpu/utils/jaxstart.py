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
  warm-up", and how many seconds of start-up were compilation. That
  figure (substratus_jax_compile_seconds_total) is the compiler or the
  cache read alone: it leaves out tracing the Python and lowering the
  jaxpr (a Pallas kernel included) to the module the compiler takes,
  which every process does again for every executable and which a cache
  hit does not save.
* So every build is also written down by stage and by program: JAX reports
  the start and end of each executable's trace, lowering and compile (and,
  inside the last, what the persistent cache did) to one jax.monitoring
  listener, which turns each into a finished span on the span log
  (jax.trace, jax.lower, jax.compile, under the span current on the thread
  that builds) and into substratus_jax_build_seconds_total by stage and
  program. The listener runs only while JAX builds something.
* The process's way to ready is a handful of phases (`phase`): each a span
  on the same log, an annotation in a profiler capture, and one series of
  substratus_startup_seconds. startup_record() is all of it as plain data
  (docs/observability.md "Start-up").
* Device memory, which only the process that holds the chip can read, is
  offered as gauges (the server's /metrics) and as one line (the trainer).
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
from typing import Dict, Optional

from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.observability.tracing import tracer

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
DEVICE_LINE_PREFIX = "jax devices: "
MEMORY_LINE_PREFIX = "jax device memory: "

# jax.monitoring event names (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py). The compile event wraps compile_or_get_cached: it
# fires once for every executable built, whether the compiler ran or the
# persistent cache answered; the cache's own events fire inside it, on the
# same thread, and carry no name.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _COMPILE_EVENT: "compile",
}
# The compile stage's two parts in the counter: reading the cache's entry
# (a hit) and everything else (the key, and on a miss the compiler).
BUILD_STAGES = ("trace", "lower", "cache_read", "compile")
# JAX names the lowered module after the function: jit(decode) -> decode.
_WRAPPED_NAME = re.compile(r"^\w+\((.*)\)$")

METRICS.describe(
    "substratus_jax_compilations_total",
    "Executables built since start-up (a new shape or program; includes "
    "loads from the persistent compilation cache).", type="counter",
)
METRICS.describe(
    "substratus_jax_compile_seconds_total",
    "Seconds spent building those executables (compiler or cache read). "
    "Leaves out tracing and lowering, which a cache hit does not save: "
    "substratus_jax_build_seconds_total has every stage.",
    type="counter",
)
METRICS.describe(
    "substratus_jax_compile_cache_hits_total",
    "Executables the persistent compilation cache answered.",
    type="counter",
)
METRICS.describe(
    "substratus_jax_build_seconds_total",
    "Seconds spent building executables, by stage (trace: Python to jaxpr; "
    "lower: jaxpr to the compiler's module, Pallas kernels included; "
    "cache_read: a persistent-cache hit's read; compile: the rest of the "
    "backend's compile, the compiler itself on a miss) and by program (the "
    "jitted function's name). No second is in two series.",
    type="counter",
)
METRICS.describe(
    "substratus_jax_builds_total",
    "Executables built, by program and by what the persistent compilation "
    "cache did (hit|miss|off); sums to substratus_jax_compilations_total.",
    type="counter",
)
METRICS.describe(
    "substratus_startup_seconds",
    "Wall seconds of each start-up phase of this process (serve.start, "
    "startup.backend, startup.load, startup.quantize, startup.engine, "
    "engine.build.*; before_backend: process start to startup.backend's).",
    type="gauge",
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
    """Feed the counters and the span log from jax.monitoring
    (idempotent). JAX reports a stage's start (a scalar), then what happens
    inside it, then its duration and its time span, all on the thread that
    builds: the state between those calls is per thread."""
    global _listening
    if _listening:
        return
    import jax.monitoring

    local = threading.local()

    def on_start(event: str, value: float, **kwargs) -> None:
        if event in STAGE_OF_EVENT:
            local.depth = getattr(local, "depth", 0) + 1

    def on_duration(event: str, seconds: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            METRICS.inc("substratus_jax_compilations_total")
            METRICS.inc("substratus_jax_compile_seconds_total", by=seconds)
        elif event == _CACHE_READ_EVENT:
            local.cache_read_s = seconds

    def on_event(event: str, **kwargs) -> None:
        if event == _CACHE_HIT_EVENT:
            METRICS.inc("substratus_jax_compile_cache_hits_total")
            local.cache = "hit"
        elif event == _CACHE_MISS_EVENT:
            local.cache = "miss"

    def on_span(event: str, start: float, end: float, fun_name: str = "",
                **kwargs) -> None:
        stage = STAGE_OF_EVENT.get(event)
        if stage is None:
            return
        # (a stage that was open when the listener was registered ends
        # with no start counted: never below none open)
        local.depth = max(getattr(local, "depth", 1) - 1, 0)
        attrs = {}
        if stage == "compile":
            attrs = {"cache": getattr(local, "cache", "off"),
                     "cache_read_s": getattr(local, "cache_read_s", 0.0)}
            local.cache, local.cache_read_s = "off", 0.0
        if local.depth > 0:
            # Built inside another stage (jnp's own jitted helpers traced
            # while a program is traced or lowered): that stage's seconds
            # hold these already.
            return
        wrapped = _WRAPPED_NAME.match(fun_name)
        program = wrapped.group(1) if wrapped else fun_name
        tracer.record_span("jax." + stage, start, end, program=program,
                           **attrs)
        by_stage = {stage: max(end - start, 0.0)}
        if stage == "compile":
            METRICS.inc("substratus_jax_builds_total",
                        {"program": program, "cache": attrs["cache"]})
            read_s = min(attrs["cache_read_s"], by_stage["compile"])
            if read_s:
                by_stage = {"cache_read": read_s,
                            "compile": by_stage["compile"] - read_s}
        for name, seconds in by_stage.items():
            METRICS.inc("substratus_jax_build_seconds_total",
                        {"stage": name, "program": program}, by=seconds)

    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_time_span_listener(on_span)
    _listening = True


@contextlib.contextmanager
def phase(name: str, **span_kw):
    """One phase of the process's way to ready: a span on the span log
    (`span_kw` as for tracer.span), a jax.profiler.TraceAnnotation of the
    same name (a flag check unless a capture runs), and its wall seconds
    in substratus_startup_seconds{phase}. Yields the span."""
    t0 = time.perf_counter()
    with tracer.span(name, **span_kw) as span:
        from jax.profiler import TraceAnnotation

        try:
            with TraceAnnotation(name):
                yield span
        finally:
            METRICS.set("substratus_startup_seconds",
                        time.perf_counter() - t0, {"phase": name})


def process_age_s() -> Optional[float]:
    """Seconds since the operating system started this process (None where
    /proc does not say): what no clock of the program's own can count, the
    interpreter's start and the imports before its first line."""
    try:
        with open("/proc/self/stat") as f:
            # after "pid (comm) ": state is field 3, starttime field 22
            started_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - started_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def startup_record() -> dict:
    """What this process's start was made of, as plain data from the
    metrics registry (so it outlives the span ring): seconds by phase, the
    seconds before startup.backend, and every program built with its
    seconds by stage. Stages as in substratus_jax_build_seconds_total;
    `executables` and `cache_hits` are the two old counters."""
    phases = {labels["phase"]: seconds for labels, seconds
              in METRICS.series("substratus_startup_seconds")}
    builds: Dict[str, dict] = {}

    def build(program: str) -> dict:
        return builds.setdefault(program, {
            "program": program,
            **{stage + "_s": 0.0 for stage in BUILD_STAGES},
            "cache": None, "count": 0, "cache_hits": 0,
        })

    totals = {stage: 0.0 for stage in BUILD_STAGES}
    for labels, seconds in METRICS.series("substratus_jax_build_seconds_total"):
        build(labels["program"])[labels["stage"] + "_s"] += seconds
        totals[labels["stage"]] += seconds
    for labels, n in METRICS.series("substratus_jax_builds_total"):
        b = build(labels["program"])
        b["count"] += int(n)
        b["cache_hits"] += int(n) if labels["cache"] == "hit" else 0
        b["cache"] = labels["cache"] if b["cache"] in (None, labels["cache"]) \
            else "mixed"
    return {
        "phases": phases,
        "before_backend_s": phases.pop("before_backend", None),
        "builds": sorted(
            builds.values(),
            key=lambda b: -sum(b[stage + "_s"] for stage in BUILD_STAGES)),
        "totals": totals,
        "executables": int(
            METRICS.get("substratus_jax_compilations_total") or 0),
        "cache_hits": int(
            METRICS.get("substratus_jax_compile_cache_hits_total") or 0),
    }


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
    device line on stdout (DEVICE_LINE_PREFIX + a JSON object), as the
    phase startup.backend; the seconds the process had lived before it
    (the interpreter, the entry point's imports) go on its span as
    `before_s`. Returns the device summary."""
    before_s = process_age_s()
    with phase("startup.backend") as span:
        if before_s is not None:
            span.set_attribute("before_s", round(before_s, 3))
            METRICS.set("substratus_startup_seconds", before_s,
                        {"phase": "before_backend"})
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
