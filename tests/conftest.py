"""Test configuration: force an 8-device virtual CPU mesh before JAX is used.

Mirrors the reference's trick of testing the control plane without real
infrastructure (reference: internal/controller/main_test.go uses envtest +
faked Job/Pod status instead of a kubelet): here we test TPU sharding logic
without TPUs by giving XLA 8 virtual host devices. The chip itself is
reached only through chip_smoke.py.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402
from substratus_tpu.ops.kvcache import insert_prefill

# The TPU compiler a `v5e` test loads would log under /tmp otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md "Tier-1 verify")
    config.addinivalue_line(
        "markers", "slow: a sweep tier-1 does not count on")


def greedy_decode(module, params, cfg, prompt, max_tokens, cache_len=256):
    """Shared greedy-decode oracle: prefill, seed the cache, step. The one
    reference implementation of the cache-seeding contract for tests.
    (test_serve/test_int8_kv compare per-step logits and keep their own
    step loops.)"""
    import jax.numpy as jnp

    tokens = jnp.asarray([prompt], jnp.int32)
    logits, kv = module.forward(params, tokens, cfg)
    cache = module.init_cache(cfg, 1, cache_len)
    n = len(prompt)
    cache = insert_prefill(cache, kv, n)
    out = [int(logits[0, -1].argmax())]
    pos = n
    while len(out) < max_tokens:
        lg, cache = module.decode_step(
            params, cache,
            jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32), cfg,
        )
        out.append(int(lg[0].argmax()))
        pos += 1
    return out


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Run every pl.pallas_call traced under this test in interpret mode.

    The kernels never pick interpret mode themselves (on a backend that is
    not a TPU they would otherwise serve interpreted, silently). A CPU test
    that reaches a kernel through the model or the engine, where there is
    no `interpret` argument to pass, asks for it here."""
    from jax.experimental import pallas as pl

    compiled = pl.pallas_call

    def interpreted(*args, **kwargs):
        kwargs["interpret"] = True
        return compiled(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", interpreted)


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e:2x2 host, with the persistent
    compilation cache off: an executable compiled for a described device
    is written there but cannot be read back without one, and the next
    compile would warn. For the `test_chip_compile*.py` files alone, and
    never autouse: the worker that runs one of them loads the TPU's
    library and keeps it. Several workers do so side by side only under
    `ALLOW_MULTIPLE_LIBTPU_LOAD=1`, which the tier-1 command sets; without
    it run those files in one process."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, no topology: skip
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def mesh8():
    """A 2x2x2 (data, fsdp, tensor) mesh over 8 virtual CPU devices."""
    from substratus_tpu.parallel.mesh import build_mesh

    return build_mesh(data=2, fsdp=2, tensor=2)


_COLLECTIVE_PROBE = {}  # session cache: {"ok": bool, "why": str}


def multiprocess_collectives_available():
    """Capability probe: can this backend run a 2-process
    jax.distributed gang with a real broadcast collective? Some CPU
    jaxlib builds cannot ("Multiprocess computations aren't implemented
    on the CPU backend") — gang tests there must SKIP with that reason,
    not fail, so the tier-1 dot count only moves on real regressions
    (docs/development.md "Tests"). Probed ONCE per session by running
    tools/collective_probe.py as an actual 2-process gang; returns
    (ok, reason)."""
    if not _COLLECTIVE_PROBE:
        import json
        import socket
        import subprocess
        import sys
        import tempfile

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(repo, "tools", "collective_probe.py")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        tmp = tempfile.mkdtemp(prefix="collective_probe_")
        procs, outs = [], []
        for pid in range(2):
            out = os.path.join(tmp, f"probe{pid}.json")
            outs.append(out)
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, worker,
                        "--pid", str(pid), "--nprocs", "2",
                        "--coord", f"127.0.0.1:{port}", "--out", out,
                    ],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE, text=True,
                )
            )
        ok, why = True, ""
        try:
            for p in procs:
                _, stderr = p.communicate(timeout=180)
                if p.returncode != 0 and ok:
                    tail = [
                        ln for ln in stderr.strip().splitlines() if ln.strip()
                    ]
                    ok, why = False, (tail[-1] if tail else
                                      f"probe rc={p.returncode}")
            if ok:
                for out in outs:
                    if not json.load(open(out)).get("ok"):
                        ok, why = False, "broadcast delivered wrong bytes"
        except subprocess.TimeoutExpired:
            ok, why = False, "probe gang hung (collective never returned)"
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()
        _COLLECTIVE_PROBE.update(ok=ok, why=why)
    return _COLLECTIVE_PROBE["ok"], _COLLECTIVE_PROBE["why"]


@pytest.fixture(scope="session")
def multiprocess_collectives():
    """Skip-gate fixture for tests that need a jax.distributed gang but
    don't go through run_gang (which probes on its own)."""
    ok, why = multiprocess_collectives_available()
    if not ok:
        pytest.skip(f"multi-process collectives unavailable: {why}")


def run_gang(worker_path, tmp_path, extra=(), nprocs=2, devs_per_proc=2,
             timeout=900):
    """Launch a jax.distributed gang of `nprocs` worker subprocesses and
    collect their JSON result files. One harness for every multihost
    test (serving, training, 70B north-star). Backends without
    multi-process collectives SKIP here (capability probe above) with
    the backend's own error as the reason."""
    import json
    import socket
    import subprocess
    import sys

    ok, why = multiprocess_collectives_available()
    if not ok:
        pytest.skip(f"multi-process collectives unavailable: {why}")

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devs_per_proc}"
    )
    procs, outs = [], []
    for pid in range(nprocs):
        out = tmp_path / f"gang{pid}.json"
        outs.append(out)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, str(worker_path),
                    "--pid", str(pid), "--nprocs", str(nprocs),
                    "--coord", f"127.0.0.1:{port}",
                    "--out", str(out), *extra,
                ],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
        )
    results = []
    try:
        for p, out in zip(procs, outs):
            _, stderr = p.communicate(timeout=timeout)
            assert p.returncode == 0, (
                f"gang worker failed:\n{stderr[-3000:]}"
            )
            results.append(json.loads(out.read_text()))
    finally:
        # One worker failing must not orphan the rest blocked in the
        # distributed rendezvous/broadcast (they'd hold the port and CPU
        # for the init timeout).
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return results
