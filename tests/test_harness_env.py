"""utils/childenv.py: the environment and the bounded run a launcher that
stays off JAX (chip_smoke.py) gives its children."""
import sys

from substratus_tpu.utils import childenv


def test_child_env_platform_handling():
    base = {"JAX_PLATFORMS": "tpu", "PYTHONPATH": "/opt/extra",
            "HOME": "/root"}
    # The chip path: inherit EVERYTHING verbatim — the child must see the
    # backend the parent was started for.
    inherited = childenv.child_env(base=base)
    assert inherited == base
    assert inherited is not base  # a copy; mutating it can't leak back
    # The rehearsal path: platform pinned, the rest untouched.
    pinned = childenv.child_env(platform="cpu", base=base)
    assert pinned["JAX_PLATFORMS"] == "cpu"
    assert pinned["PYTHONPATH"] == "/opt/extra"
    assert pinned["HOME"] == "/root"


def test_merge_host_device_flag_rewrites_not_clobbers():
    env = {"XLA_FLAGS": "--xla_foo=1 "
           "--xla_force_host_platform_device_count=2 --xla_bar=0"}
    childenv.merge_host_device_flag(env, 8)
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert env["XLA_FLAGS"].count("host_platform_device_count") == 1
    assert "--xla_foo=1" in env["XLA_FLAGS"]
    assert "--xla_bar=0" in env["XLA_FLAGS"]


def test_rehearsal_env_differs_only_in_the_pinned_delta():
    """A multi-chip rehearsal child differs from an inheriting one ONLY in
    the two keys child_env owns (platform pin, host-device flag)."""
    base = {
        "JAX_PLATFORMS": "tpu", "PYTHONPATH": "/opt/extra",
        "TPU_NAME": "local", "XLA_FLAGS": "--xla_foo=1",
    }
    chip = childenv.child_env(base=base)
    rehearsal = childenv.child_env(platform="cpu", host_devices=4, base=base)
    assert chip == base
    delta = {
        k for k in set(chip) | set(rehearsal)
        if chip.get(k) != rehearsal.get(k)
    }
    assert delta == {"JAX_PLATFORMS", "XLA_FLAGS"}
    assert rehearsal["XLA_FLAGS"] == (
        "--xla_foo=1 --xla_force_host_platform_device_count=4"
    )


def test_run_child_watchdog_classifies_hang_error_and_ok():
    ok = childenv.run_child(
        [sys.executable, "-c", "print('hi')"], timeout_s=30
    )
    assert ok.ok and ok.rc == 0 and ok.stdout.strip() == "hi"
    err = childenv.run_child(
        [sys.executable, "-c",
         "import sys; print('boom', file=sys.stderr); sys.exit(3)"],
        timeout_s=30,
    )
    assert not err.ok and err.rc == 3 and "boom" in err.stderr
    assert not err.hung
    hung = childenv.run_child(
        [sys.executable, "-c", "import time; time.sleep(60)"],
        timeout_s=0.5,
    )
    assert hung.hung and hung.rc is None and not hung.ok
    assert hung.elapsed_s < 10.0
