#!/usr/bin/env python
"""Exposition-format lint (make metrics-lint).

Imports every instrumented plane (serve engine, server gauges, train
telemetry, controller runtime, SCI client) so their metric declarations
register, synthesizes representative traffic — including label values that
need escaping — renders the shared registry, and validates the output with
observability.lint_exposition: unique families, HELP/TYPE before samples,
parseable samples, escaped labels, +Inf histogram buckets.

Exits non-zero listing each problem. Runs without jax/device access: only
the declaration modules are imported, nothing jitted.
"""
import os
import sys

sys.dont_write_bytecode = True
# Runnable from a bare checkout (no pip install -e .): the repo root is
# this file's parent directory.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    # Register every plane's declarations (import side effects only).
    import substratus_tpu.controller.rollout  # noqa: F401
    import substratus_tpu.controller.runtime  # noqa: F401
    import substratus_tpu.gateway.router  # noqa: F401
    import substratus_tpu.rl.learner  # noqa: F401
    import substratus_tpu.rl.loop  # noqa: F401
    import substratus_tpu.sci.client as sci
    import substratus_tpu.serve.engine  # noqa: F401
    import substratus_tpu.serve.server  # noqa: F401
    from substratus_tpu.observability import METRICS, lint_exposition

    # Synthetic traffic across all three kinds, with hostile label values.
    METRICS.inc("substratus_reconcile_total", {"kind": "Model"})
    METRICS.inc(
        "substratus_reconcile_errors_total",
        {"kind": 'we"ird\\kind\nname'},
    )
    METRICS.set("substratus_workqueue_depth", 3)
    METRICS.observe("substratus_reconcile_seconds", 0.012, {"kind": "Model"})
    # Gateway plane: the shared HTTP counter + per-replica series whose
    # label values carry URL characters (scheme colon, slashes).
    METRICS.inc(
        "substratus_http_requests_total",
        {"endpoint": "/v1/completions", "code": "429"},
    )
    METRICS.set(
        "substratus_gateway_inflight", 2, {"replica": "http://r0:8080"}
    )
    METRICS.inc("substratus_gateway_sheds_total", {"reason": "ratelimit"})
    # Serve-engine speculation plane (serve/engine.py _spec_drain): the
    # proposed/accepted pair the acceptance-rate recording rule divides.
    METRICS.inc("substratus_serve_spec_proposed_tokens_total", by=3)
    METRICS.inc("substratus_serve_spec_accepted_tokens_total", by=2)
    METRICS.inc(
        "substratus_gateway_ejections_total", {"replica": "http://r0:8080"}
    )
    METRICS.observe("substratus_gateway_upstream_seconds", 0.05)
    # Fleet telemetry plane (gateway/fleet.py + observability/timeline.py
    # + observability/sketch.py): drive the aggregator and an SLO
    # tracker for real so the per-replica gauges, drop counters, bubble
    # counter, and burn counter all render through the same exposition.
    from substratus_tpu.gateway.fleet import FleetAggregator
    from substratus_tpu.gateway.loadreport import LoadReport
    from substratus_tpu.observability.sketch import SLOTracker
    from substratus_tpu.observability.timeline import StepTimeline

    fleet = FleetAggregator()
    fleet.record(
        "http://r0:8080",
        LoadReport(queue_depth=2, active_slots=3, max_slots=4, seq=1,
                   wall_ts=__import__("time").time()),
    )
    fleet.record(  # out-of-order: exercises the dropped counter
        "http://r0:8080", LoadReport(seq=1), now=1.0,
    )
    fleet.record_shed("http://r0:8080")
    fleet.signals()
    slo = SLOTracker()
    slo.observe("ttft", 5.0)  # over budget: burns
    # Request-journey plane (observability/journey.py): record a short
    # lifecycle so the per-type event counter renders, and attach an
    # exemplar trace id to a breaching TTFT observation so the exemplar
    # store exercises alongside the histogram sample it annotates.
    from substratus_tpu.observability.journey import RequestJourney

    j = RequestJourney(rid="lint-req", origin="lint")
    for ev in ("submit", "admit", "prefill", "dispatch", "drain", "emit"):
        j.record(ev)
    j.breach("ttft", 5.0, 2.0)
    j.record("end", reason="stop")
    METRICS.inc("substratus_serve_slo_exemplars_total", {"slo": "ttft"})
    METRICS.observe(
        "substratus_serve_ttft_seconds", 5.0, exemplar=j.trace_id
    )
    # Hot weight-swap + rollout plane (serve/engine.py swap_params,
    # controller/rollout.py) and the RL loop (rl/): drive every
    # outcome label + the version gauge through the exposition.
    METRICS.inc(
        "substratus_serve_weight_swaps_total", {"outcome": "applied"}
    )
    METRICS.inc(
        "substratus_serve_weight_swaps_total", {"outcome": "rejected"}
    )
    METRICS.set("substratus_serve_weights_version", 3)
    METRICS.inc(
        "substratus_rollout_swaps_total", {"outcome": "applied"}
    )
    METRICS.inc(
        "substratus_rollout_runs_total", {"outcome": "complete"}
    )
    METRICS.inc("substratus_rl_learner_updates_total")
    METRICS.inc("substratus_rl_episodes_total", by=4)
    METRICS.set("substratus_rl_learner_loss", 1.25)
    METRICS.inc("substratus_rl_rounds_total")
    METRICS.set("substratus_rl_mean_reward", 0.5)
    # Autoscale plane (controller/autoscale.py): an applied and a
    # frozen decision so the outcome counter and target gauge render.
    from substratus_tpu.controller.autoscale import (
        Autoscaler,
        AutoscalePolicy,
        ScaleTargets,
    )

    scaler = Autoscaler(AutoscalePolicy(
        sustain_up_s=0.0, up_cooldown_s=0.0,
    ))
    scaler.plan(fleet.signals(), ScaleTargets(replicas=1), now=1.0)
    scaler.plan(None, ScaleTargets(replicas=1), now=2.0)  # frozen
    # The first iteration sets the floor; the second's gap over it
    # renders the bubble counter.
    tl = StepTimeline()
    tl.record_iteration(t_start=0.0, wall_s=0.015, dispatch_s=0.001)
    tl.record_iteration(
        t_start=0.02, wall_s=0.02, admit_s=0.004, admitted=1,
        dispatch_s=0.001, drain_s=0.01,
    )
    client = sci.FakeSCIClient()
    client.get_object_md5("gs://bucket", "obj")
    client.create_signed_url("gs://bucket", "obj", "d41d8cd9")
    from substratus_tpu.train.telemetry import StepLogger

    StepLogger(
        n_params=10_000, tokens_per_step=1024, peak_flops=1e12,
        emit=lambda line: None,
    ).log_step(0, loss=1.0, step_seconds=0.1, last=True)

    text = METRICS.render()
    problems = lint_exposition(text)
    names = [
        line.split(" ")[2]
        for line in text.splitlines()
        if line.startswith("# TYPE ")
    ]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        problems.append(f"duplicate family declarations: {sorted(dupes)}")
    if problems:
        for p in problems:
            print(f"metrics-lint: {p}", file=sys.stderr)
        return 1
    print(
        f"metrics-lint: ok ({len(names)} families, "
        f"{len(text.splitlines())} lines)"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
