"""A model family is files: benchmarks/families/<family>.py beside
benchmarks/reference/<family>.py, found by the configuration's `family`.

The proof that a second family arrives as files only (a copy of the tree,
the fixture family of tests/benchmarks/fixtures/second_family/ added to it,
nothing that was there edited), the generic weight maker on that family's
table, the llama family's tree and readings pinned from the parent commit
before anything was moved, and the greps that keep the seam: system.py
alone imports the program, and no harness code knows a block.
"""
import ast
import gzip
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest
from test_bench_scopes import _f  # the hand encoder of the profiler's wire format

from benchmarks.harness import counts, manifest as M
from benchmarks.harness import trace_reduce as TR
from benchmarks.harness import trace_scopes as TS
from benchmarks.harness import weights as W

HERE = Path(__file__).parent
FIXTURES = HERE / "fixtures"
SECOND = FIXTURES / "second_family"
PARENT = json.loads((FIXTURES / "parent_readings.json").read_text())
TRACES = {"longdoc_0.2s": HERE / "trace_mistral7b_longdoc_0.2s.xplane.pb.gz",
          "chat_0.3s_scopes": HERE / "trace_mistral7b_chat_0.3s_scopes.xplane.pb.gz"}
CELL = "two-stack.newmix"


def _config(name, rehearse=False):
    cfg = json.loads((M.BENCH / "configs" / f"{name}.json").read_text())
    if rehearse:
        cfg = dict(cfg, **{k: v for k, v in cfg["rehearse"].items() if k != "why"})
    return cfg


def _env():
    env = dict(os.environ, PYTHONPATH=str(M.ROOT), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


# -- (a) a second family arrives as files only -----------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the fixture family, a configuration of
    it, a mix, a reader and appended entries. Returns (root, manifest,
    bytes of every file that was there)."""
    root = tmp_path_factory.mktemp("family") / "tree"
    shutil.copytree(M.ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(M.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    # new files: the family, its reference, a reader
    for src in SECOND.rglob("*.py"):
        dst = root / src.relative_to(SECOND)
        assert not dst.exists()
        shutil.copy(src, dst)
    # a configuration of that family, and a mix
    cfg = _config("mistral-7b-instruct-v0.2")
    cfg["family"] = "two_stack"
    (root / "benchmarks/configs/two-stack.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "benchmarks/traffic/chat.json").read_text())
    mix.update(loop="closed", clients=3, plan_rate_rps=5.0, block=6,
               why="a new mix, as data")
    mix["rehearse"].update(clients=3, block=6, plan_rate_rps=40.0)
    (root / "benchmarks/traffic/newmix.json").write_text(json.dumps(mix))
    # appended entries
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({
        "name": "two-stack", "source": cfg["source"],
        "file": "benchmarks/configs/two-stack.json", "reduced": [],
        "why": "a configuration of a family added as files"})
    man["workloads"].append({
        "name": CELL, "config": "two-stack", "traffic": "newmix", "chips": 1,
        "why": "a cell of a family added as files"})
    man["per_layer"].append({
        "name": "family_step_bytes", "unit": "bytes", "better": "lower",
        "source": "program_counter", "layer": "kernels", "moves": "tok_per_s",
        "workloads": [CELL]})
    for m in man["end_to_end"]:
        if m["name"] == "tok_per_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    before.pop(root / "BENCHMARK.json")  # entries are appended to it
    return root, man, before


def _untouched(before):
    return all(p.read_bytes() == b for p, b in before.items())


def test_a_second_family_is_valid_as_files(tree):
    root, man, before = tree
    assert M.validate(man, root) == []
    fam = M.family_of({"family": "two_stack"}, root)
    assert fam.__file__ == str(root / "benchmarks/families/two_stack.py")
    assert M.reference_of({"family": "two_stack"}, root).served_gaps
    assert {f.__name__.rsplit(".", 1)[1] for f in M.families(root)} == {
        "llama_family", "two_stack"}
    assert _untouched(before)


def test_a_second_family_rehearses_correct_with_its_own_counts(tree):
    root, man, before = tree
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed",
         str(2**31 + 26), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["workload"] == CELL
    assert line["failed"] == 0 and line["counts"]["check_tokens"] > 0
    # the count is the fixture's function's, not the llama family's
    cfg = _config("mistral-7b-instruct-v0.2", rehearse=True)
    fam = M.family_of({"family": "two_stack"}, root)
    llama = M.family_of(cfg)
    got = line["counts"]["per_layer"]["family_step_bytes"]["value"]
    assert got == fam.decode_step_bytes(cfg, [10, 20], 2)
    d = fam.dims(cfg)["D"]
    lead = 2 * d + (2 * d * d + 4 * 2 * d) + 4 * 8  # norm, int8 + scales, bias
    assert got == llama.decode_step_bytes(cfg, [10, 20], 2) + lead
    assert _untouched(before)


def _xspace(region):
    """One decode step with one op inside `region`, inside `layers`."""
    plane = _f(1, 2) + _f(2, "/device:TPU:0")
    plane += _f(5, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "tf_op")))
    op = (_f(1, 7) + _f(2, "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8] %p)")
          + _f(5, _f(1, 1) + _f(5, f"jit(decode)/layers/while/body/{region}/dot_general:")))
    plane += _f(4, _f(1, 7) + _f(2, op))
    plane += _f(4, _f(1, 8) + _f(2, _f(1, 8) + _f(2, "jit_decode(1)")))
    ops = (_f(1, 3) + _f(2, "XLA Ops") + _f(3, 1000)
           + _f(4, _f(1, 7) + _f(2, 5_000_000) + _f(3, 2_000_000)))
    mods = (_f(1, 2) + _f(2, "XLA Modules") + _f(3, 1000)
            + _f(4, _f(1, 8) + _f(2, 4_000_000) + _f(3, 5_000_000)))
    return _f(1, plane + _f(3, mods) + _f(3, ops))


@pytest.mark.parametrize("where,want", [("copy", "moe.shared"),
                                        ("repo", "layers")])
def test_a_familys_region_is_charged_to_its_own_name(tree, tmp_path, where, want):
    """trace_scopes' table, as PERF.md section 5 is made from it: where the
    fixture family's file is present an op inside its region goes to that
    name; where it is not, to the innermost base name. Never `unscoped`."""
    root, _, before = tree
    pb = tmp_path / "one.xplane.pb"
    pb.write_bytes(_xspace("moe.shared"))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.harness.trace_scopes", str(pb)],
        cwd=root if where == "copy" else M.ROOT, env=_env(),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    scopes = json.loads(out.stdout)["programs"]["jit_decode(1)"]["scopes"]
    assert set(scopes) == {want} and scopes[want]["ms"] == pytest.approx(2e-3)
    assert _untouched(before)


def test_a_count_a_family_does_not_define_is_not_borrowed(tree, tmp_path,
                                                          monkeypatch):
    """The fixture family defines decode_step_bytes and MATMUL_SCOPES only:
    over a recorded trace, the readers of its other counts return nothing,
    and those it defines read its own."""
    root, _, _ = tree
    fam = M.family_of({"family": "two_stack"}, root)
    cfg = _config("mistral-7b-instruct-v0.2")
    run = _run_over(tmp_path, monkeypatch, "chat_0.3s_scopes", "own", cfg, fam)
    assert M.layer_reader("prefill_mxu_share")(run) is None
    assert M.layer_reader("prefill_mxu_share.tok")(run) is None
    assert M.layer_reader("decode_weights_hbm_share")(run) is None
    llama = dict(run, family=M.family_of(cfg))
    assert M.layer_reader("prefill_mxu_share")(llama) is not None
    share = M.layer_reader("decode_hbm_share")
    assert share(run) > share(llama) > 0
    assert M.layer_reader("decode_matmul_ms")(run) == pytest.approx(
        M.layer_reader("decode_matmul_ms")(llama))  # no moe.shared op in it
    assert M.layer_reader("decode_matmul_ms")(dict(run, family=None)) is None


# -- (b) the generic maker on that family's table --------------------------------

@pytest.fixture(scope="module")
def two_stack(tree):
    root, _, _ = tree
    cfg = _config("mistral-7b-instruct-v0.2", rehearse=True)
    table = M.family_of({"family": "two_stack"}, root).leaf_table(cfg)
    return table, W.make_weights(table, 2**31 + 26)


def test_paths_nest_to_any_depth(two_stack):
    table, w = two_stack
    assert set(w) == {"tok_embed", "out_norm", "lm_head", "layers", "lead",
                      "route_bias"}
    assert set(w["lead"]) == {"in_norm", "w_in", "gate"}
    assert set(w["lead"]["gate"]) == {"bias"}
    for path, leaf in table.items():
        got = W.at(w, path)
        got = got["q"] if leaf.kind == "int8" else got
        assert tuple(got.shape) == tuple(leaf.shape), path
    shapes = W.tree_shapes(table)
    assert W.at(shapes, "lead/w_in")["scale"].shape == (1, 1, 128)
    assert W.nest({"a/b/c": 1, "a/d": 2, "e": 3}) == {
        "a": {"b": {"c": 1}, "d": 2}, "e": 3}


def test_kinds_hold_their_types(two_stack):
    table, w = two_stack
    want = {"norm": "bfloat16", "normal": "bfloat16", "bias": "float32"}
    for path, leaf in table.items():
        got = W.at(w, path)
        if leaf.kind == "int8":
            assert str(got["q"].dtype) == "int8", path
            assert str(got["scale"].dtype) == "float32", path
            assert got["scale"].shape == W.scale_shape(leaf), path
        else:
            assert str(got.dtype) == want[leaf.kind], path
        shape = W.at(W.tree_shapes(table), path)
        shape = shape["q"] if leaf.kind == "int8" else shape
        assert str(shape.dtype) == ("int8" if leaf.kind == "int8"
                                    else want[leaf.kind]), path
    norm = np.asarray(w["lead"]["in_norm"].astype(np.float32))
    assert 0.5 < norm.min() < norm.max() < 1.5 and norm.std() > 0.01
    bias = np.asarray(w["route_bias"])
    assert 0 < np.abs(bias).max() < 0.1
    by_bytes = counts.weight_bytes(table)
    assert by_bytes["route_bias"] == 4 * 8 and by_bytes["lead/in_norm"] == 2 * 64
    with pytest.raises(ValueError, match="unknown kind"):
        W.make_weights({"x": W.Leaf((2,), (), 0, "fp4")}, 1)


def test_a_seed_repeats_and_two_seeds_differ(two_stack):
    table, w = two_stack
    again = W.make_weights(table, 2**31 + 26)
    other = W.make_weights(table, 2**31 + 27)
    for path, leaf in table.items():
        a, b, c = (W.at(t, path) for t in (w, again, other))
        if leaf.kind == "int8":
            a, b, c = a["q"], b["q"], c["q"]
        assert np.array_equal(np.asarray(a), np.asarray(b)), path
        assert not np.array_equal(np.asarray(a), np.asarray(c)), path


# -- (c) the llama family's tree is the parent's, bit for bit ---------------------

def _tree_sha256(tree) -> str:
    flat = {}

    def walk(t, path):
        if isinstance(t, dict):
            for k in t:
                walk(t[k], path + (k,))
        else:
            flat["/".join(path)] = t

    walk(tree, ())
    h = hashlib.sha256()
    for path in sorted(flat):
        x = flat[path]
        a = np.asarray(x.view("uint16") if str(x.dtype) == "bfloat16" else x)
        for part in (path, str(x.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("key", sorted(PARENT["tree_sha256"]))
def test_the_llama_tree_from_a_seed_is_the_parents(key):
    name, seed = key.split("@")
    cfg = _config(name, rehearse=True)
    table = M.family_of(cfg).leaf_table(cfg)
    assert _tree_sha256(W.make_weights(table, int(seed))) == PARENT[
        "tree_sha256"][key]


# -- (d) the readers over the recorded traces give the parent's numbers ------------

def _records():
    def rec(prompt_len, submit, first, done, n):
        return types.SimpleNamespace(
            planned=types.SimpleNamespace(prompt_len=prompt_len),
            submit=submit, first=first, done=done,
            sink=types.SimpleNamespace(ts=[first + 0.04 * i for i in range(n)]))

    return [rec(1100, 10.2, 10.4, None, 30), rec(520, 10.6, 10.8, 11.05, 6),
            rec(64, 10.9, 11.0, None, 3), rec(2304, 9.0, 9.5, 10.1, 12)]


def _run_over(tmp_path, monkeypatch, trace, cell, cfg, family):
    """A run's dict over a recorded trace, as benchmarks/run.py builds it."""
    d = tmp_path / ".bench_out" / "trace" / cell / "plugins" / "profile" / "r"
    d.mkdir(parents=True)
    with gzip.open(TRACES[trace], "rb") as src, open(d / "vm.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.chdir(tmp_path)
    TS._CACHE.clear()  # keyed by the relative path, which repeats here
    return {"cell": {"name": cell}, "config": cfg, "family": family,
            "mix": M.traffic_of("chat"), "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "records": _records(),
            "w0": 10.0, "w1": 12.0, "traced": (10.5, 11.5), "rehearse": False,
            "trace": TR.reduce_dir(str(d.parents[2]))}


@pytest.mark.parametrize("key", sorted(PARENT["readers"]))
def test_readers_over_the_recorded_traces_give_the_parents_numbers(
        key, tmp_path, monkeypatch):
    trace, name = key.split("|")
    cfg = _config(name)
    run = _run_over(tmp_path, monkeypatch, trace, "cell-" + trace, cfg,
                    M.family_of(cfg))
    want = PARENT["readers"][key]
    got = {metric: M.layer_reader(metric)(run) for metric in want}
    assert got == want  # exactly: the same arithmetic in the same order
    assert sum(v is not None for v in want.values()) >= 4


# -- (e) a family without one of its files is refused by name ---------------------

@pytest.mark.parametrize("gone,needle", [
    ("benchmarks/families/two_stack.py", "has no benchmarks/families/two_stack.py"),
    ("benchmarks/reference/two_stack.py", "has no benchmarks/reference/two_stack.py"),
    (None, "no `family` in benchmarks/configs/two-stack.json"),
])
def test_validate_names_the_missing_family_file(tree, tmp_path, gone, needle):
    root, man, _ = tree
    copy = tmp_path / "tree"
    shutil.copytree(root, copy, ignore=shutil.ignore_patterns("__pycache__"))
    if gone:
        (copy / gone).unlink()
    else:
        path = copy / "benchmarks/configs/two-stack.json"
        cfg = json.loads(path.read_text())
        del cfg["family"]
        path.write_text(json.dumps(cfg))
    bad = M.validate(man, copy)
    assert len(bad) == 1 and needle in bad[0] and "two-stack" in bad[0], bad
    assert M.validate(man, root) == []


# -- the seam stays where it is ------------------------------------------------------

def _sources(*globs):
    return sorted(p for g in globs for p in M.BENCH.glob(g))


def test_system_py_alone_imports_the_program():
    importers = set()
    for path in _sources("**/*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            names = ([a.name for a in node.names] if isinstance(node, ast.Import)
                     else [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            if any(n.split(".")[0] == "substratus_tpu" for n in names):
                importers.add(str(path.relative_to(M.BENCH)))
    assert importers == {"harness/system.py"}
    # the fixture family keeps to the same rule
    for path in SECOND.rglob("*.py"):
        assert "import substratus_tpu" not in path.read_text()
        assert "from substratus_tpu" not in path.read_text()


def test_no_harness_code_knows_the_llama_block():
    """String literals and attribute names of run.py, harness/ and
    layer_metrics/: none is a leaf of the llama table, a model-shape key
    its family file reads, or the program's config class. The base region
    vocabulary (a copy of ops/scopes.py) and `vocab_size` (the prompt ids)
    stay."""
    cfg = _config("mixtral-8x7b-instruct-v0.1")
    leaves = {p.split("/")[-1] for p in M.family_of(cfg).leaf_table(cfg)}
    keys = {"hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "num_hidden_layers",
            "num_local_experts", "num_experts_per_tok", "tie_word_embeddings",
            "rope_theta", "rms_norm_eps", "max_position_embeddings",
            "model_dims", "llama_config", "LlamaConfig", "llama"}
    assert {"wq", "w_gate", "router", "attn_norm", "lm_head"} <= leaves
    forbidden = (leaves | keys) - set(TS.SCOPES)
    found = {}
    for path in _sources("run.py", "harness/*.py", "layer_metrics/*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            word = (node.value if isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    else node.attr if isinstance(node, ast.Attribute)
                    else node.id if isinstance(node, ast.Name) else None)
            if word in forbidden:
                found.setdefault(path.name, set()).add(word)
    assert found == {}
