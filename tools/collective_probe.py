"""Multi-process collective capability probe (one gang worker).

Launched N times by tests/conftest.py's capability probe to answer ONE
question before any gang test runs: can this backend actually execute a
jax.distributed multi-process collective? Some CPU jaxlib builds
cannot — there the gang tests must SKIP with that reason instead of
failing, so the tier-1 dot count reflects
real regressions (docs/development.md "Tests").

    python tools/collective_probe.py --pid 0 --nprocs 2 \
        --coord 127.0.0.1:9911 --out /tmp/probe0.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pid", type=int, required=True)
    ap.add_argument("--nprocs", type=int, required=True)
    ap.add_argument("--coord", required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()

    import jax

    jax.distributed.initialize(
        coordinator_address=a.coord,
        num_processes=a.nprocs,
        process_id=a.pid,
    )
    import numpy as np
    from jax.experimental import multihost_utils

    # The exact collective the lockstep scheduler rides
    # (serve/multihost.py StepSync): leader's buffer must arrive intact
    # on every process.
    buf = np.arange(16, dtype=np.uint8) if a.pid == 0 else np.zeros(
        16, np.uint8
    )
    out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    ok = out.tolist() == list(range(16))
    with open(a.out, "w") as f:
        json.dump({"ok": bool(ok), "pid": a.pid}, f)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
