"""Structured train-loop telemetry: step histograms, throughput, MFU.

Replaces the train loop's bare `print(f"step {i} loss ...")` with a
`log_step` path that (1) observes step time and tokens/sec into the shared
registry — the exact signals the edge-accelerator characterization papers
compare on (PAPERS.md) — (2) derives an MFU gauge from tokens-per-step when
the chip's peak FLOPs are known, and (3) emits one machine-parseable JSON
line per logging interval, so log pipelines stop regex-scraping progress.
"""
from __future__ import annotations

import json
import logging
import time
from typing import Optional

import jax

from substratus_tpu.observability.metrics import (
    METRICS,
    RATIO_BUCKETS,
    THROUGHPUT_BUCKETS,
)
from substratus_tpu.observability.tracing import tracer

log = logging.getLogger("substratus.train")

METRICS.histogram(
    "substratus_train_step_seconds",
    "Wall time of one optimizer step, device-synchronized (seconds).",
)
METRICS.histogram(
    "substratus_train_tokens_per_second",
    "Training throughput per step (global batch tokens / step seconds).",
    buckets=THROUGHPUT_BUCKETS,
)
METRICS.histogram(
    "substratus_train_mfu_ratio",
    "Model FLOPs utilization per step (6*N*tokens / peak), when the "
    "device's peak FLOPs are known.",
    buckets=RATIO_BUCKETS,
)
METRICS.histogram(
    "substratus_train_phase_seconds",
    "Wall time of one train-loop phase (seconds), labeled by phase: "
    "data_load (next batch from the dataset), step (the optimizer step, "
    "device-synchronized), checkpoint (checkpoint save, 0 when the step "
    "saved nothing).",
)
for _name, _help in (
    ("substratus_train_step", "Last completed optimizer step."),
    ("substratus_train_loss", "Loss at the last completed step."),
    ("substratus_train_mfu", "MFU at the last completed step (0 when the "
     "device's peak FLOPs are unknown)."),
):
    METRICS.describe(_name, _help, type="gauge")

# Per-chip dense peak FLOPs (bf16), for the MFU denominator (Google Cloud
# documentation of each part). A CPU run reports mfu=0; a TPU whose kind
# is not listed is an error, not a run whose MFU silently drops out.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_peak_flops() -> Optional[float]:
    """Aggregate peak FLOPs of every addressable-or-not device in the run;
    None off the TPU (CPU test meshes). KeyError for a TPU device kind
    with no table entry."""
    devices = jax.devices()
    kind = devices[0].device_kind
    if kind not in PEAK_FLOPS:
        if devices[0].platform != "tpu":
            return None
        raise KeyError(
            f"no peak FLOPs for device_kind {kind!r}; add it to "
            "train/telemetry.py::PEAK_FLOPS with its source"
        )
    return PEAK_FLOPS[kind] * len(devices)


class StepLogger:
    """Per-step telemetry sink for the train loop.

    `tokens_per_step` is the GLOBAL batch in tokens (batch_size * seq_len);
    `n_params` drives the standard 6*N*tokens FLOPs estimate (forward +
    backward for a dense decoder; attention FLOPs excluded, consistent
    with how MFU is quoted in the scaling literature)."""

    def __init__(
        self,
        n_params: int,
        tokens_per_step: int,
        peak_flops: Optional[float] = None,
        log_every: int = 10,
        emit=None,  # line sink, default print (flushes; container logs)
    ):
        self.n_params = int(n_params)
        self.tokens_per_step = int(tokens_per_step)
        self.peak_flops = peak_flops
        self.log_every = max(1, log_every)
        self._emit = emit or (lambda line: print(line, flush=True))
        self._t_start = time.perf_counter()

    def log_step(
        self, step: int, loss: float, step_seconds: float,
        last: bool = False,
        data_seconds: Optional[float] = None,
        checkpoint_seconds: Optional[float] = None,
    ) -> Optional[dict]:
        """Record one completed step. Histograms update every step; the
        JSON progress line is emitted every `log_every` steps (and on the
        final step). Returns the emitted record, or None.

        data_seconds / checkpoint_seconds are the step's phase splits
        (train/main.py times them around next(data) and maybe_save); when
        given they land in substratus_train_phase_seconds and on the JSON
        record, so a slow run triages to input pipeline vs device step vs
        checkpoint I/O from the artifact alone."""
        step_seconds = max(step_seconds, 1e-9)
        tps = self.tokens_per_step / step_seconds
        METRICS.observe("substratus_train_step_seconds", step_seconds)
        METRICS.observe("substratus_train_tokens_per_second", tps)
        METRICS.observe(
            "substratus_train_phase_seconds", step_seconds,
            {"phase": "step"},
        )
        if data_seconds is not None:
            METRICS.observe(
                "substratus_train_phase_seconds", data_seconds,
                {"phase": "data_load"},
            )
        if checkpoint_seconds is not None:
            METRICS.observe(
                "substratus_train_phase_seconds", checkpoint_seconds,
                {"phase": "checkpoint"},
            )
        mfu = 0.0
        if self.peak_flops:
            mfu = (6.0 * self.n_params * self.tokens_per_step) / (
                step_seconds * self.peak_flops
            )
            METRICS.observe("substratus_train_mfu_ratio", mfu)
        METRICS.set("substratus_train_step", step)
        METRICS.set("substratus_train_loss", float(loss))
        METRICS.set("substratus_train_mfu", mfu)
        if step % self.log_every and not last:
            return None
        record = {
            "event": "train_step",
            "step": step,
            "loss": round(float(loss), 6),
            "step_seconds": round(step_seconds, 4),
            "tokens_per_second": round(tps, 1),
            "mfu": round(mfu, 4),
            "elapsed_seconds": round(
                time.perf_counter() - self._t_start, 1
            ),
        }
        if data_seconds is not None:
            record["data_seconds"] = round(data_seconds, 4)
        if checkpoint_seconds is not None:
            record["checkpoint_seconds"] = round(checkpoint_seconds, 4)
        # Log/trace join: inside a span (train/main.py wraps the run in
        # `train.run`, itself parented from the spawning controller's
        # TRACEPARENT) every progress line names its trace — grep a slow
        # step's trace_id straight out of the container logs.
        ctx = tracer.current_context()
        if ctx is not None:
            record["trace_id"] = ctx.trace_id
            record["span_id"] = ctx.span_id
        self._emit(json.dumps(record, separators=(",", ":")))
        return record
