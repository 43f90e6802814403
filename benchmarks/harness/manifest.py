"""BENCHMARK.json and the data files it names.

A cell names its configuration, its traffic mix and its chips; a metric
names its cells. Everything that belongs to one configuration, one mix or
one per-layer metric is a file of its own, found by that name:

    benchmarks/configs/<config>.json       (the manifest gives the path)
    benchmarks/traffic/<traffic>.json
    benchmarks/layer_metrics/<metric>.py   one read(run) -> number | None
    benchmarks/families/<family>.py        the configuration's `family`: its
                                           sizes, weight tree, program
                                           config, counts and region names
    benchmarks/reference/<family>.py       that family's plain reference

so a later PR adds files and appends entries, and edits nothing. Every
module found by name is loaded by its path under the given `root`, so one
added in a copy of the tree is found there.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r} in BENCHMARK.json "
        f"(have: {', '.join(w['name'] for w in manifest['workloads'])})"
    )


def config_of(manifest: Dict[str, Any], name: str, root: Path = ROOT
              ) -> Dict[str, Any]:
    for c in manifest["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str, root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "benchmarks" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def reports(metric: Dict[str, Any], cell_name: str) -> bool:
    """Does this cell report this metric? No `workloads` key: every cell."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def metrics_for(manifest: Dict[str, Any], cell_name: str, kind: str
                ) -> List[Dict[str, Any]]:
    return [m for m in manifest[kind] if reports(m, cell_name)]


@functools.lru_cache(maxsize=None)
def _module(path: Path, name: str):
    """A module of the benchmark's own, by its file; loaded once a process
    (a reference keeps its compiled functions)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(name: str, root: Path = ROOT
                 ) -> Callable[[Any], Optional[float]]:
    """The metric's own reader: benchmarks/layer_metrics/<name>.py::read."""
    path = root / "benchmarks" / "layer_metrics" / f"{name}.py"
    try:
        return _module(path, "benchmarks.layer_metrics."
                       + re.sub(r"[^A-Za-z0-9_]", "_", name)).read
    except FileNotFoundError:
        raise FileNotFoundError(
            f"no reader for per-layer metric {name}: {path}") from None


def family_file(family: str, root: Path = ROOT) -> Path:
    return root / "benchmarks" / "families" / f"{family}.py"


def reference_file(family: str, root: Path = ROOT) -> Path:
    return root / "benchmarks" / "reference" / f"{family}.py"


def family_of(config: Dict[str, Any], root: Path = ROOT):
    """What the benchmark knows of the configuration's block, by its
    `family`: benchmarks/families/<family>.py."""
    return _module(family_file(config["family"], root),
                   "benchmarks.families." + config["family"])


def reference_of(config: Dict[str, Any], root: Path = ROOT):
    """The configuration's plain reference, by its `family`."""
    return _module(reference_file(config["family"], root),
                   "benchmarks.reference." + config["family"])


def families(root: Path = ROOT) -> List[Any]:
    """Every family file present, by name."""
    return [_module(p, "benchmarks.families." + p.stem) for p in
            sorted((root / "benchmarks" / "families").glob("*.py"))
            if p.stem != "__init__"]


def _family_files(entry: Dict[str, Any], root: Path) -> List[str]:
    """A configuration's `family` has to have both of its files."""
    try:
        with open(root / entry["file"]) as f:
            family = json.load(f).get("family")
    except OSError:
        return []  # no file: said above
    except ValueError as e:
        return [f"config {entry['name']}: {entry['file']} is not JSON ({e})"]
    if not isinstance(family, str) or not NAME_RE.match(family):
        return [f"config {entry['name']}: no `family` in {entry['file']}"]
    return [f"config {entry['name']}: family {family} has no "
            f"{p.relative_to(root)}"
            for p in (family_file(family, root), reference_file(family, root))
            if not p.is_file()]


def validate(manifest: Dict[str, Any], root: Path = ROOT) -> List[str]:
    """What the contract would refuse, as a list of sentences (empty: fine).
    Checked by the tests, and before every run."""
    bad: List[str] = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return bad
    names = lambda rows: [r.get("name") for r in rows]  # noqa: E731
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = names(manifest[kind])
        for n in ns:
            if not isinstance(n, str) or not NAME_RE.match(n):
                bad.append(f"{kind}: name {n!r} has characters outside the contract")
        if len(set(ns)) != len(ns):
            bad.append(f"{kind}: a name appears twice")
    both = names(manifest["end_to_end"]) + names(manifest["per_layer"])
    if len(set(both)) != len(both):
        bad.append("a metric name is used end to end and per layer")
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for c in manifest["configs"]:
        if not (root / c["file"]).is_file():
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in manifest["paths"]):
            bad.append(f"config {c['name']}: file outside paths")
        bad.extend(_family_files(c, root))
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        used.add(w["config"])
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME_RE.match(w["traffic"]):
            bad.append(f"cell {w['name']}: traffic name {w['traffic']!r}")
        elif not (root / "benchmarks" / "traffic" / f"{w['traffic']}.json").is_file():
            bad.append(f"cell {w['name']}: no traffic file for {w['traffic']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"cell {w['name']}: why is not one line of 1..200 characters")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: config and traffic pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    for c in configs:
        if c not in used:
            bad.append(f"config {c}: used by no cell")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for four chips")
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(str(m.get("unit", ""))):
            bad.append(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        for wn in m.get("workloads", []):
            if wn not in cells:
                bad.append(f"metric {m['name']}: unknown cell {wn}")
    for m in manifest["end_to_end"]:
        if set(m) - {"name", "unit", "better", "bound", "source", "workloads"}:
            bad.append(f"metric {m['name']}: unknown keys")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: end-to-end source {m['source']}")
        if not 0.01 <= float(m.get("bound", 0)) <= 0.1:
            bad.append(f"metric {m['name']}: bound {m.get('bound')}")
    for m in manifest["per_layer"]:
        if set(m) - {"name", "unit", "better", "source", "layer", "moves", "workloads"}:
            bad.append(f"metric {m['name']}: unknown keys")
        if m.get("moves") not in e2e:
            bad.append(f"metric {m['name']}: moves unknown metric {m.get('moves')}")
            continue
        if not m.get("layer") or "\n" in m["layer"] or len(m["layer"]) > 200:
            bad.append(f"metric {m['name']}: layer")
        if not (root / "benchmarks" / "layer_metrics" / f"{m['name']}.py").is_file():
            bad.append(f"metric {m['name']}: no reader file")
        for wn in cells:
            if reports(m, wn) and not reports(e2e[m["moves"]], wn):
                bad.append(
                    f"metric {m['name']}: cell {wn} reports it but not "
                    f"{m['moves']}, which it moves"
                )
    for wn in cells:
        e = [m for m in manifest["end_to_end"] if reports(m, wn)]
        if len(e) < 2 or not any(m["name"] == "setup_s" for m in e):
            bad.append(f"cell {wn}: needs setup_s and one more end-to-end metric")
        if not any(reports(m, wn) for m in manifest["per_layer"]):
            bad.append(f"cell {wn}: reports no per-layer metric")
    return bad
