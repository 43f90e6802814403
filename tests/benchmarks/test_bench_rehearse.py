"""The CPU rehearsal of every cell at a tiny size, the refusal to measure
without a TPU, and a whole run with the timed path broken underneath."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as M

MAN = M.load()
CELLS = [w["name"] for w in MAN["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run"] + args, cwd=M.ROOT, env=env,
        capture_output=True, text=True, timeout=600, **kw)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(cell, trace):
    out = _run(["--workload", cell, "--seed", str(2**31 + 11), "--seconds",
                "2", "--trace", str(trace), "--rehearse"])
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # counts only: a CPU run writes nothing under a device metric's name
    assert line["metrics"] == {} and line["rehearse"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= M.cell(MAN, cell)["chips"]
    c = line["counts"]
    assert c["compiles_in_window"] == 0 and c["preemptions"] == 0
    assert c["requests_first_token"] > 0 and c["check_tokens"] > 0
    assert any(l.startswith("lengths: ") for l in lines)  # the histogram
    assert any(l.startswith("correct: ") for l in lines)  # numbers and limits
    if trace:
        names = {m["name"] for m in M.metrics_for(MAN, cell, "per_layer")
                 if m["source"] != "device_trace"}
        assert set(c["per_layer"]) <= names
        assert "itl_p95_ms.recorded" in c["per_layer"]


def test_measuring_path_refuses_to_run_without_a_tpu():
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert out.returncode != 0
    assert "no TPU here" in out.stdout
    last = out.stdout.strip().splitlines()[-1]
    assert not last.startswith("{")  # no result line


def test_unknown_workload_is_refused():
    out = _run(["--workload", "nope", "--rehearse"])
    assert out.returncode != 0 and "no workload" in out.stdout


def test_rate_override_changes_the_open_loop_rate_and_nothing_else():
    """`--rate`, the builder's tool for the one sweep that finds the rate."""
    from benchmarks import run as R
    from benchmarks.harness import traffic as T

    _, _, _, mix = R.resolve("mistral-7b.chat")
    _, _, _, swept = R.resolve("mistral-7b.chat", rate=3.5)
    assert swept["rate_rps"] == 3.5 and mix["rate_rps"] != 3.5
    assert {k: v for k, v in swept.items() if k != "rate_rps"} == {
        k: v for k, v in mix.items() if k != "rate_rps"}
    a, b = T.plan(mix, 5, mix["block"]), T.plan(swept, 5, mix["block"])
    assert [(p.prompt_len, p.output_len) for p in a] == [
        (p.prompt_len, p.output_len) for p in b]  # the same work, sooner
    assert b[-1].due_s < a[-1].due_s
