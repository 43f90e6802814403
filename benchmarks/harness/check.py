"""The comparison that decides `correct`.

Once the window has closed: a sample, drawn from the seed, of the requests
the window finished, with the longest in it. The plain float32 reference
(`benchmarks/reference/<family>.py`) runs once over each prompt with its
served tokens, and for every served token the comparison reads how far its
reference logit lies below the reference's best at that position. The
served tokens are what the timed path produced at the timed sizes: the
engine's own prefill chunks, paged cache and batched decode steps, with as
many slots in use as the window had. Every request is greedy, so a sound
run serves the reference's best token or one that bfloat16 rounding cannot
tell from it.

Three numbers, each with its own limit. Two from the configuration's file
(`correct.gap_max`, `correct.gap_mean`; PERF.md gives the readings each
was set from): the widest gap, and the mean gap over the sample's tokens.
The mean is the steady one; the widest swings by its nature and catches a
single wrong token. The third is exact, with the limit 0: how many of the
types the configuration states under `precision` (weights, activations,
KV cache) the engine does not hold. Rounding the cache or the activations
to int8 moves the served tokens less than bfloat16's own rounding does, so
no gap can tell it; the types can. A request that did not serve exactly
the tokens asked is counted under `failed`, not here.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def _finished(records: List[Any], w0: float, w1: float) -> List[Any]:
    done = [
        r for r in records
        if r.done is not None and w0 <= r.done < w1 and r.first is not None
        and len(r.sink.ids) == r.planned.output_len
    ]
    return sorted(done, key=lambda r: r.planned.index)


def sample_finished(records: List[Any], w0: float, w1: float, seed: int,
                    k: int) -> List[Any]:
    """k requests that finished inside the window with every token asked
    for: the longest (prompt + output), the one that boarded with most
    others decoding (the engine fills its lowest free slot, so that one sat
    in the highest), and the rest drawn from the seed."""
    done = _finished(records, w0, w1)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.planned.prompt_len
                                       + r.planned.output_len, -r.planned.index))
    spans = [(r.first, r.done) for r in records
             if r.first is not None and r.done is not None]

    def others(r) -> int:
        return sum(1 for a, b in spans if a < r.first < b)

    picked = [longest]
    rest = [r for r in done if r is not longest]
    if rest and k > 1:
        picked.append(max(rest, key=lambda r: (others(r), -r.planned.index)))
        rest = [r for r in rest if r is not picked[1]]
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.permutation(len(rest))[: max(0, k - len(picked))]
    return picked + [rest[i] for i in sorted(pick)]


def compare(reference, weights, cfg: Dict[str, Any], sample: List[Any],
            limits: Dict[str, float], stated: Optional[Dict[str, str]] = None,
            found: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Numbers beside their limits, and the verdict. `stated` is the
    configuration's `precision`, `found` the types the engine holds."""
    all_gaps = [reference.served_gaps(weights, cfg, r.prompt, r.sink.ids)
                for r in sample]
    if not all_gaps:
        return {"correct": False, "why": "no request finished in the window",
                "tokens": 0, "requests": 0, "numbers": {}}
    g = np.concatenate(all_gaps)
    numbers = {
        "gap_max": {"value": float(g.max()), "limit": float(limits["gap_max"])},
        "gap_mean": {"value": float(g.mean()), "limit": float(limits["gap_mean"])},
    }
    out: Dict[str, Any] = {}
    if stated is not None:
        other = {k: [stated.get(k), v] for k, v in (found or {}).items()
                 if stated.get(k) != v}
        numbers["precision_other_than_stated"] = {"value": float(len(other)),
                                                  "limit": 0.0}
        out["precision"] = {"stated": {k: stated.get(k) for k in found or {}},
                            "found": found}
    ok = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
             for n in numbers.values())
    return {
        "correct": bool(ok), "tokens": int(g.size), "requests": len(sample),
        "numbers": numbers,
        "served_is_best_share": float(np.mean(g == 0.0)), **out,
    }
