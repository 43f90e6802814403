"""The benchmark's one command.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process for every run: it finds the cell in BENCHMARK.json, loads the
cell's configuration and traffic mix (data files) and the configuration's
family (benchmarks/families/<family>.py), makes weights and inputs from
--seed, builds the engine `serve.main` would build, warms the
shapes this cell's traffic uses and no others, ramps, measures for
--seconds, compares a sample of what the window served with the plain
reference, and prints one JSON object as the last line of its output.

Without a TPU, or with fewer chips than the cell asks for, it prints one
line saying so and exits non-zero with no result. `--rehearse` is the CPU
rehearsal at a tiny size: it prints counts only, under `counts`, names the
device, and writes no device metric.

Not used by the driver (tools for the builder of a benchmark PR):
`--control int4|w8a8|int8kv` runs the same cell with one of the program's
own lower-precision paths switched on, which has to come out not correct;
`--rate` overrides an open-loop mix's rate for the one sweep that finds it.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

OUT_DIR = ".bench_out"  # traces; inside the checkout, git-ignored
TRACE_SECONDS = 3.0


def _say(*a) -> None:
    print(*a, flush=True)


def _parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="benchmarks.run", description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--rate", type=float, default=None)
    return ap.parse_args(argv)


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    out.update(over)
    return out


def resolve(workload: str, rehearse: bool = False,
            rate: Optional[float] = None):
    """The cell with its configuration and traffic mix, as data; at the
    rehearsal size with `rehearse`."""
    from benchmarks.harness import manifest as M

    man = M.load()
    bad = M.validate(man)
    if bad:
        raise ValueError("BENCHMARK.json is not valid:\n  " + "\n  ".join(bad))
    cell = M.cell(man, workload)
    cfg = M.config_of(man, cell["config"])
    mix = M.traffic_of(cell["traffic"])
    if rehearse:
        cfg = _merge(cfg, {k: v for k, v in cfg["rehearse"].items() if k != "why"})
        mix = _merge(mix, mix.get("rehearse", {}))
    if rate is not None:
        mix = _merge(mix, {"rate_rps": rate})
    return man, cell, cfg, mix


def main(argv=None) -> int:
    args = _parse(argv)
    try:
        man, cell, cfg, mix = resolve(args.workload, args.rehearse, args.rate)
    except (ValueError, KeyError) as e:
        _say(str(e))
        return 2
    chips = int(cell["chips"])
    seconds = float(args.seconds if args.seconds is not None
                    else man["run_seconds"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={max(chips, 1)}"
            ).strip()
    try:
        from benchmarks.harness import system
        device = system.startup()
    except ImportError as e:
        _say(f"the system under test is not in this checkout: {e}")
        return 4
    if not args.rehearse and device["platform"] != "tpu":
        _say(f"no TPU here (JAX reports {device['platform']}): the benchmark "
             "measures on the chip only; --rehearse is the CPU rehearsal")
        return 3
    if device["count"] < chips:
        _say(f"cell {cell['name']} asks for {chips} chips and JAX reports "
             f"{device['count']}")
        return 3

    result = run_once(man, cell, cfg, mix, chips, args.seed, seconds,
                      bool(args.trace), args.rehearse, args.control, device)
    # the contract's line: the last line of the run's output
    _say(json.dumps(result))
    return 0


def run_once(man, cell, cfg, mix, chips: int, seed: int, seconds: float,
             trace: bool, rehearse: bool, control: Optional[str],
             device: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from benchmarks.harness import check, manifest as M, metrics, system
    from benchmarks.harness import traffic as T
    from benchmarks.harness import weights as W

    wall0, perf0 = time.time(), time.perf_counter()
    t_process = perf0 - (wall0 - T_PROCESS)  # process start, on perf_counter
    sizes = mix["engine"]
    vocab = int(cfg["vocab_size"])
    family = M.family_of(cfg)
    table = family.leaf_table(cfg)
    annotate = None
    if trace:
        annotate = jax.profiler.TraceAnnotation

    # -- load: weights on the device from the seed, then the engine --------
    mesh = system.build_mesh(cfg, chips)
    shardings = system.weight_shardings(family, cfg, mesh)
    params = W.make_weights(table, seed, shardings)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()
    if control == "int4":
        params = system.lower_weights(params, table)
    engine = system.build_engine(family, cfg, sizes, params, mesh, control)
    found = system.precision_found(engine, table)
    engine.start()
    counters = system.Counters(engine)

    # -- the plan and its pre-built requests --------------------------------
    ramp_budget = float(mix.get("ramp_s", 0.0)) if mix["loop"] == "open" \
        else float(mix.get("ramp_budget_s", 30.0))
    n = T.planned_count(mix, ramp_budget + seconds + 5.0)
    planned = T.plan(mix, seed, n)

    def build(p: T.Planned) -> T.Record:
        prompt = T.prompt_tokens(seed, p.index, p.prompt_len, vocab)
        sink = T.Sink(annotate=annotate)
        rec = T.Record(planned=p, prompt=prompt, sink=sink)
        rec.request = system.new_request(prompt, p.output_len, sink,
                                         f"r{p.index}")
        return rec

    records: List[T.Record] = [build(p) for p in planned]
    _say("lengths: " + json.dumps(T.length_histogram(planned)))

    # -- warm-up: this cell's own shapes, counted as set-up -----------------
    buckets = T.prefill_buckets([p.prompt_len for p in planned],
                                int(sizes["max_prefill_len"]))
    warm = []
    for j, b in enumerate(buckets):
        sink = T.Sink()
        req = system.new_request(
            T.prompt_tokens(seed, j, b, vocab, stream=4), 3, sink, f"warm{j}")
        engine.submit(req)
        warm.append(sink)
    _wait(lambda: all(s.done_ts is not None for s in warm), 1500, engine,
          "warm-up")
    snap_warm = counters.snapshot()
    _say(f"warm-up: prefill buckets {buckets}, "
         f"{snap_warm['substratus_jax_compilations_total']:.0f} executables, "
         f"weights {t_weights - perf0:.1f} s, "
         f"ready {time.perf_counter() - perf0:.1f} s")

    # -- ramp, then the window ----------------------------------------------
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        gen = T.Generator(
            mix=mix, records=records, submit_fn=engine.submit,
            build_block=lambda b: [build(p) for p in T.plan_block(mix, seed, b)],
            annotate=annotate)
        gen.start()
        if mix["loop"] == "open":
            w0 = gen.t0 + float(mix["ramp_s"])
            _sleep_until(w0, engine, gen)
        else:
            clients = int(mix["clients"])
            firsts = [next(r for r in records if r.planned.client == c)
                      for c in range(clients)]
            _wait(lambda: all(r.done is not None for r in firsts),
                  600, engine, "ramp", gen)
            w0 = time.perf_counter()
        snap0 = counters.snapshot()
        w1 = w0 + seconds
        trace_dir = None
        traced = (None, None)
        if trace:
            trace_s = min(TRACE_SECONDS, seconds / 2)
            t_on = w0 + (seconds - trace_s) / 2
            _sleep_until(t_on, engine, gen)
            trace_dir = str(Path(OUT_DIR) / "trace" / cell["name"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            # no Python call tracing: it made the generator thread run
            # 300 ms late (PR 23); runtime events and the harness's own
            # annotations still say what the host was doing
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            a = time.perf_counter()
            _sleep_until(a + trace_s, engine, gen)
            b = time.perf_counter()
            jax.profiler.stop_trace()
            traced = (a, b)
        _sleep_until(w1, engine, gen)
        snap1 = counters.snapshot()
        gen.stop()
        # Edge requests: wait for the first token of what was submitted (for
        # the prompt-credit rule), then cancel what is still decoding.
        live = [r for r in records if r.submit is not None and not r.refused]
        _wait(lambda: all(r.first is not None or r.done is not None
                          for r in live), 30, engine, "edge", soft=True)
        t_close = w1
        for r in live:
            if r.done is None:
                r.request.cancelled = True
        _wait(lambda: all(r.done is not None for r in live), 30, engine,
              "drain", soft=True)
        engine.stop()
    finally:
        gc.enable()
        gc.unfreeze()
    if gen.error is not None:
        raise gen.error
    if engine.error is not None:
        raise engine.error

    # -- the device as it is after the window -------------------------------
    mem = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": int(peak)}

    # -- end-to-end numbers --------------------------------------------------
    e2e = metrics.end_to_end(records, w0, w1)
    e2e["setup_s"] = w0 - t_process
    out = metrics.outcome(records, t_close, float(mix["deadline_s"]))
    delta = system.Counters.delta(snap0, snap1)
    _say("window: " + json.dumps({
        "seconds": seconds, "requests_first_token": e2e["_samples"]["ttft"],
        "gaps": e2e["_samples"]["itl"], **out,
        "concurrency_peak": metrics.concurrency_peak(records, w0, w1),
        "queue_wait_p50_ms": _ms(metrics.percentile(
            metrics.queue_waits(records, w0, w1, system.queue_wait_s), 50)),
        "gen_late_p99_ms": _ms(metrics.percentile(
            metrics.lateness(records, w0, w1), 99)),
        "preemptions": delta["stats"].get("preemptions", 0),
        "compiles_in_window": delta["substratus_jax_compilations_total"],
        "ramp_s": w0 - gen.t0, "blocks_built_in_run": gen.blocks_added,
    }))

    # -- correct: free the program's state, then the reference -------------
    # (a control run served with other weights: the reference reads the
    # configuration's own, made again from the seed)
    engine.cache = None
    if control == "int4":
        engine.params = params = None
        params = W.make_weights(table, seed, shardings)
    reference = M.reference_of(cfg)
    sample = check.sample_finished(records, w0, w1, seed,
                                   int(mix["check_requests"]))
    t_chk = time.perf_counter()
    verdict = check.compare(reference, params, cfg, sample, cfg["correct"],
                            stated=cfg["precision"], found=found)
    _say(("control: " if control else "correct: ") + json.dumps(
        {**verdict, "control": control,
         "seconds": time.perf_counter() - t_chk}))
    correct = bool(verdict["correct"]) and out["failed"] == 0

    result: Dict[str, Any] = {
        "correct": correct, "attempted": out["attempted"],
        "failed": out["failed"], "metrics": {}, "device": dev,
        "workload": cell["name"], "seed": seed,
    }
    if rehearse:
        result["rehearse"] = True
        result["counts"] = {
            "requests_first_token": e2e["_samples"]["ttft"],
            "gaps": e2e["_samples"]["itl"], "finished": out["finished"],
            "check_tokens": verdict["tokens"],
            "compiles_in_window": delta["substratus_jax_compilations_total"],
            "preemptions": delta["stats"].get("preemptions", 0),
            "prefill_buckets": buckets,
        }
    if not trace and not rehearse:
        for m in M.metrics_for(man, cell["name"], "end_to_end"):
            v = e2e.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    if trace:
        from benchmarks.harness import trace_reduce

        reduced = None
        if trace_dir is not None:
            reduced = trace_reduce.reduce_dir(trace_dir)
        run = {
            "cell": cell, "config": cfg, "family": family, "mix": mix,
            "chips": chips,
            "device": dev, "memory": mem,
            "records": records, "w0": w0, "w1": w1, "traced": traced,
            "counters": delta, "trace": reduced, "e2e": e2e,
            "rehearse": rehearse,
            "queue_wait_s": system.queue_wait_s,
        }
        for m in M.metrics_for(man, cell["name"], "per_layer"):
            v = M.layer_reader(m["name"])(run)
            if v is not None and not (rehearse and m["source"] == "device_trace"):
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        if rehearse:
            result["counts"]["per_layer"] = result.pop("metrics")
            result["metrics"] = {}
        if reduced is not None and reduced.get("busy_s"):
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["top_ops"][:10],
                "idle_gaps": reduced["idle_gaps"][:10],
            }
    return result


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else seconds * 1e3


def _check_alive(engine, gen=None) -> None:
    if engine.error is not None:
        raise RuntimeError(f"the engine died: {engine.error!r}")
    if gen is not None and gen.error is not None:
        raise RuntimeError(f"the generator died: {gen.error!r}")


def _sleep_until(t: float, engine, gen=None) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))
        _check_alive(engine, gen)


def _wait(cond, timeout_s: float, engine, what: str, gen=None,
          soft: bool = False) -> None:
    t_end = time.perf_counter() + timeout_s
    while not cond():
        _check_alive(engine, gen)
        if time.perf_counter() > t_end:
            if soft:
                return
            raise RuntimeError(f"{what} did not finish in {timeout_s:.0f} s")
        time.sleep(0.02)


if __name__ == "__main__":
    sys.exit(main())
