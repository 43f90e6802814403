"""chip_smoke.py rehearsed on the CPU, and the start-up helper whose lines
it reads (utils/jaxstart.py).

The smoke's real run needs the chip (the builder's chip tool; the driver
runs it after every PR). What tier-1 can hold is its control flow: at
`tiny` size on the CPU, with the kernels in interpret mode, every phase
runs through the same entry points, children and checks — and without the
explicit rehearsal choice a CPU is refused, with no result line.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from substratus_tpu.utils import jaxstart

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _start_smoke(out_dir, *args, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, SMOKE, "--out", str(out_dir), *args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO,
    )


def test_chip_smoke_refuses_a_cpu_without_the_rehearsal_choice(tmp_path):
    proc = _start_smoke(tmp_path / "out")
    stdout, _ = proc.communicate(timeout=120)
    assert proc.returncode != 0
    assert "not on a TPU" in stdout
    assert '"ok"' not in stdout
    # It stopped at the first child, not after serving a 1.1B model on CPU.
    assert "phase serve" not in stdout


def _checkout_cache_listing():
    try:
        return sorted(os.listdir(jaxstart.CHECKOUT_CACHE_DIR))
    except FileNotFoundError:
        return None


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """Both rehearsals, started together (each is a chain of children that
    leaves most cores idle; the suite has no minute to spare): {chips:
    (returncode, stdout, stderr, cache dir, in-checkout cache before)}."""
    before = _checkout_cache_listing()
    started = {}
    for chips in (1, 4):
        tmp = tmp_path_factory.mktemp(f"smoke{chips}")
        started[chips] = tmp / "cache", _start_smoke(
            tmp / "out", "--rehearse", "--chips", str(chips),
            env_extra={jaxstart.CACHE_ENV: str(tmp / "cache")},
        )
    done = {}
    try:
        for chips, (cache, proc) in started.items():
            out, err = proc.communicate(timeout=600)
            done[chips] = proc.returncode, out, err, cache, before
    finally:
        for _, proc in started.values():
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
    return done


@pytest.mark.parametrize("chips, phases", [
    (1, ["kernels", "serve", "serve-warm", "serve-int8", "train"]),
    (4, ["probe-1chip", "sharded-serve", "sharded-forward", "sharded-train"]),
])
def test_chip_smoke_rehearsal_runs_every_phase(rehearsals, chips, phases):
    """--rehearse [--chips 4]: all phases pass, the last stdout line is the
    result with the device JAX reported, and with JAX_COMPILATION_CACHE_DIR
    set the children cache there and nowhere else."""
    returncode, stdout, stderr, cache, before = rehearsals[chips]
    assert returncode == 0, stdout[-3000:] + stderr[-2000:]
    lines = stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips},
    }
    for phase in phases:
        assert f"phase {phase}: ok" in stdout, phase
    assert "FAILED" not in stdout
    assert os.listdir(cache), "no compile cache entry where the env said"
    assert _checkout_cache_listing() == before
    if chips == 1:
        # The second start of the same server read what the first compiled.
        warm = [ln for ln in lines if ln.startswith("  serve-warm: ready")]
        assert warm and "(0 from the cache" not in warm[0], warm


# --- utils/jaxstart.py ---------------------------------------------------------


@pytest.fixture
def jax_cache_config():
    """Put JAX's compilation-cache settings back after a test that places
    the persistent cache in this process: the rest of the pytest process
    compiles without writing every executable to disk."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    from jax.experimental.compilation_cache import compilation_cache

    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()  # drop the cache object it opened


def test_compile_cache_dir_comes_from_the_env_or_the_checkout(
    monkeypatch, jax_cache_config
):
    # Set from outside: JAX reads the variable itself; the code sets no
    # directory (the config keeps whatever it had).
    had = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(jaxstart.CACHE_ENV, "/some/dir")
    assert jaxstart.configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == had
    # Small executables are cached too, either way.
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    # Unset, on the CPU: no default cache (its reloads flood stderr).
    monkeypatch.delenv(jaxstart.CACHE_ENV)
    assert jaxstart.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == had

    # Unset, on an accelerator: one fixed, git-ignored directory of the
    # checkout.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jaxstart.configure_compile_cache() == jaxstart.CHECKOUT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache"
    )
    assert ".jax_cache/" in open(os.path.join(REPO, ".gitignore")).read()


def test_startup_line_and_compile_counters(
    capsys, monkeypatch, jax_cache_config
):
    from substratus_tpu.observability.metrics import METRICS

    monkeypatch.setenv(jaxstart.CACHE_ENV, "/some/dir")
    summary = jaxstart.jax_startup()
    assert summary == {"platform": "cpu", "kind": "cpu", "count": 8}
    line = capsys.readouterr().out.strip()
    assert line.startswith(jaxstart.DEVICE_LINE_PREFIX)
    assert json.loads(line[len(jaxstart.DEVICE_LINE_PREFIX):]) == {
        **summary, "compile_cache": "/some/dir",
    }
    jax.config.update("jax_compilation_cache_dir", None)  # count, not cache
    x = jnp.ones((11, 7))
    step = jax.jit(lambda x: x * 3 + 1)
    built = METRICS.get("substratus_jax_compilations_total") or 0
    step(x)  # a new executable
    after = METRICS.get("substratus_jax_compilations_total")
    assert after == built + 1
    step(x)  # the same shape: nothing is built
    assert METRICS.get("substratus_jax_compilations_total") == after
    assert METRICS.get("substratus_jax_compile_seconds_total") > 0
    # The CPU backend reports no memory: no line content, no gauges.
    assert jaxstart.device_memory() == []


# --- interpret mode is asked for, never inferred -------------------------------


class _Seen(Exception):
    pass


@pytest.fixture
def pallas_call_spy(monkeypatch):
    """pl.pallas_call replaced by a spy that reports the `interpret` it was
    given and stops there."""
    from jax.experimental import pallas as pl

    def spy(*args, **kwargs):
        raise _Seen(kwargs.get("interpret"))

    monkeypatch.setattr(pl, "pallas_call", spy)


def test_q4einsum_never_picks_interpret_mode(pallas_call_spy):
    from substratus_tpu.ops.quant4 import q4einsum, quantize4, set_q4_impl

    w = quantize4(jax.random.normal(jax.random.key(0), (512, 128)), (0,))
    x = jnp.ones((2, 5, 512), jnp.bfloat16)  # 10 rows: reaches the kernel
    prev = set_q4_impl("pallas")
    try:
        with pytest.raises(_Seen) as seen:
            q4einsum("bsd,dm->bsm", x, w)
    finally:
        set_q4_impl(prev)
    assert seen.value.args == (False,)


def test_no_kernel_infers_interpret_from_the_backend():
    """The acceptance grep: interpret mode is an argument everywhere."""
    ops = os.path.join(REPO, "substratus_tpu", "ops")
    for name in sorted(os.listdir(ops)):
        if name.endswith(".py"):
            src = open(os.path.join(ops, name)).read()
            assert "interpret = jax.default_backend" not in src, name
            assert "interpret=jax.default_backend" not in src, name
