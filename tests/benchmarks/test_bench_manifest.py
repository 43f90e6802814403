"""BENCHMARK.json against the contract, and the proof that a later PR adds
a cell with new files and one appended entry alone."""
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as M

MAN = M.load()
CELLS = [w["name"] for w in MAN["workloads"]]


def test_manifest_is_valid():
    assert M.validate(MAN) == []


def test_top_level_keys_and_command():
    assert set(MAN) == M.TOP_KEYS
    assert MAN["command"] == ["python3", "-m", "benchmarks.run"]
    assert MAN["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(M.ROOT / "BENCHMARK.json") < 64 * 1024


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_use_only_the_allowed_characters(kind):
    for row in MAN[kind]:
        assert M.NAME_RE.match(row["name"]), row["name"]
        for key in ("config", "traffic", "moves"):
            if key in row:
                assert M.NAME_RE.match(row[key])
        if "unit" in row:
            assert M.UNIT_RE.match(row["unit"]), row["unit"]
        for text in ("why", "layer", "source"):
            if text in row:
                assert 1 <= len(row[text]) <= 200 and "\n" not in row[text]


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    moved = next(x for x in MAN["end_to_end"] if x["name"] == m["moves"])
    for cell in CELLS:
        if M.reports(m, cell):
            assert M.reports(moved, cell), (metric, cell)
    assert callable(M.layer_reader(metric))
    assert m["source"] in M.SOURCES and m["layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_one_more_and_a_layer_metric(cell):
    e2e = [m["name"] for m in M.metrics_for(MAN, cell, "end_to_end")]
    assert "setup_s" in e2e and "itl_p50_ms" in e2e and len(e2e) >= 3
    assert M.metrics_for(MAN, cell, "per_layer")
    assert M.traffic_of(M.cell(MAN, cell)["traffic"])["why"]


def test_bounds_and_four_chip_share():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(
        1, len(CELLS) // 4)


def _break(fn):
    man = copy.deepcopy(MAN)
    fn(man)
    return M.validate(man)


@pytest.mark.parametrize("fn,needle", [
    (lambda m: m["workloads"][0].update(name="has space"), "characters"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda m: m["per_layer"][2].pop("workloads"), "reports it but not"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")),
     "pair appears twice"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "four chips"),
    (lambda m: m.update(extra=1), "top-level"),
    (lambda m: m["per_layer"][0].update(why="no such key"), "unknown keys"),
    (lambda m: m["end_to_end"].pop(-1), "setup_s"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "no traffic file"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="no_reader")),
     "no reader file"),
])
def test_validate_refuses(fn, needle):
    assert any(needle in line for line in _break(fn)), _break(fn)


def test_a_fourth_cell_is_added_as_data(tmp_path):
    """New files and one appended entry; no existing file is touched."""
    root = tmp_path / "tree"
    shutil.copytree(M.ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(M.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    # 1: a traffic mix, 2: a configuration, 3: a per-layer metric's reader
    mix = json.loads((root / "benchmarks/traffic/chat.json").read_text())
    mix.update(loop="closed", clients=3, plan_rate_rps=5.0, block=6,
               why="a new mix, as data")
    mix["rehearse"].update(clients=3, block=6, plan_rate_rps=40.0)
    (root / "benchmarks/traffic/newmix.json").write_text(json.dumps(mix))
    cfg = json.loads((root / "benchmarks/configs/mistral-7b-instruct-v0.2.json"
                      ).read_text())
    cfg["rehearse"]["num_hidden_layers"] = 3
    (root / "benchmarks/configs/newconfig.json").write_text(json.dumps(cfg))
    (root / "benchmarks/layer_metrics/finished_requests.py").write_text(
        "def read(run):\n"
        "    return float(sum(1 for r in run['records'] if r.done))\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({
        "name": "newconfig", "source": cfg["source"],
        "file": "benchmarks/configs/newconfig.json", "reduced": [],
        "why": "a configuration added as data"})
    man["workloads"].append({
        "name": "newconfig.newmix", "config": "newconfig",
        "traffic": "newmix", "chips": 1, "why": "a cell added as data"})
    man["per_layer"].append({
        "name": "finished_requests", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduler", "moves": "tok_per_s",
        "workloads": ["newconfig.newmix"]})
    for m in man["end_to_end"]:
        if m["name"] == "tok_per_s":
            m["workloads"].append("newconfig.newmix")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    assert M.validate(man, root) == []

    env = dict(os.environ, PYTHONPATH=str(M.ROOT), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "newconfig.newmix", "--seed", "4", "--seconds", "2", "--trace", "1",
         "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["workload"] == "newconfig.newmix"
    assert line["counts"]["per_layer"]["finished_requests"]["value"] > 0
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there was edited


def test_the_four_chip_mixtral_cell_waits_as_data(tmp_path):
    """PERF.md's first Open question: the cell's files are in the tree; the
    PR that proves it on four chips appends these entries and edits
    nothing. Rehearsed here on four virtual CPU devices."""
    root = tmp_path / "tree"
    shutil.copytree(M.ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((M.ROOT / "BENCHMARK.json").read_text())
    name = "mixtral-8x7b-instruct-v0.1"
    cfg = json.loads((root / f"benchmarks/configs/{name}.json").read_text())
    cell = "mixtral-8x7b.batch-tp4"
    man["configs"].append({
        "name": name, "source": cfg["source"],
        "file": f"benchmarks/configs/{name}.json", "reduced": [],
        "why": "8 experts top-2, 47 GB in int8: whole depth only across four chips"})
    man["workloads"].append({
        "name": cell, "config": name, "traffic": "batch-tp4", "chips": 4,
        "why": "the sharded decode step, its all-reduces and expert weights "
               "from four HBMs exist only across chips"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and not m["name"].endswith((".tok", ".recorded")):
            m["workloads"].append(cell)
    man["per_layer"].append({
        "name": "collective_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "collectives (four chips)",
        "moves": "itl_p50_ms", "workloads": [cell]})
    # a third cell may take four chips once the benchmark has four cells;
    # with three, one four-chip cell is always allowed
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    assert M.validate(man, root) == []
    env = dict(os.environ, PYTHONPATH=str(M.ROOT), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", cell, "--seed",
         "9", "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["counts"]["check_tokens"] > 0
