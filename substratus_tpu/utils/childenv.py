"""Child-process construction for programs whose children use the chip.

A chip belongs to one process at a time: a parent that has touched JAX
holds it, and a child that needs it then fails or hangs. So a launcher
(chip_smoke.py) stays off JAX and runs the server, the trainer and the
kernel phase as children, one after another, each ended and reaped before
the next starts. This module is what such a parent needs: the child's
environment, and a bounded run that classifies a timeout.

Import-light on purpose: no jax, no other substratus import.
"""
from __future__ import annotations

import os
import re
import subprocess
import time
from dataclasses import dataclass
from typing import Mapping, Optional, Sequence


def merge_host_device_flag(env: dict, n_devices: int) -> None:
    """Set ``--xla_force_host_platform_device_count=n`` in
    ``env['XLA_FLAGS']``, REWRITING any existing count (a pre-set wrong
    count must not win), preserving every other flag."""
    flags = env.get("XLA_FLAGS", "")
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", flags
    ).strip()
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()


def child_env(
    platform: Optional[str] = None,
    host_devices: Optional[int] = None,
    base: Optional[Mapping[str, str]] = None,
) -> dict:
    """The child's environment: a copy of the caller's.

    ``platform=None`` inherits ``JAX_PLATFORMS`` untouched (the chip path:
    the child sees what the parent was started with); a string pins it (a
    CPU rehearsal pins ``"cpu"``). ``host_devices`` merges the XLA
    virtual-device flag, for rehearsing a multi-chip path on the CPU."""
    env = dict(os.environ if base is None else base)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    if host_devices is not None:
        merge_host_device_flag(env, host_devices)
    return env


@dataclass
class ChildResult:
    """One watched child run. ``hung=True`` means the time limit fired
    and the child was killed."""

    rc: Optional[int]
    stdout: str
    stderr: str
    elapsed_s: float
    hung: bool = False

    @property
    def ok(self) -> bool:
        return not self.hung and self.rc == 0


def run_child(
    argv: Sequence[str],
    timeout_s: float,
    env: Optional[Mapping[str, str]] = None,
    cwd: Optional[str] = None,
) -> ChildResult:
    """Run a child to its end with captured output and a hard wall-clock
    limit. A timeout returns ``hung=True`` instead of raising
    (``subprocess.run`` kills and reaps the child on expiry), so the
    caller branches on one classification."""
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            list(argv), capture_output=True, text=True,
            timeout=timeout_s, env=dict(env) if env is not None else None,
            cwd=cwd,
        )
    except subprocess.TimeoutExpired as e:
        return ChildResult(
            rc=None,
            stdout=(e.stdout or b"").decode(errors="replace")
            if isinstance(e.stdout, bytes) else (e.stdout or ""),
            stderr=(e.stderr or b"").decode(errors="replace")
            if isinstance(e.stderr, bytes) else (e.stderr or ""),
            elapsed_s=time.monotonic() - t0,
            hung=True,
        )
    return ChildResult(
        rc=proc.returncode, stdout=proc.stdout, stderr=proc.stderr,
        elapsed_s=time.monotonic() - t0,
    )
