"""From the profiler's `.xplane.pb` to the numbers per-layer metrics read.

`jax.profiler.ProfileData` reads the file with nothing but JAX. A TPU's
plane is named "/device:TPU:<n>"; its line "XLA Modules" has one event for
every execution of a compiled program (named "jit_decode(<id>)": two
shapes of one jitted function have two ids), and its line "XLA Ops" one
event for every operation, named by the operation's whole HLO text
("%fusion.225 = bf16[65536,8,128]{...} fusion(...)"), shortened here to
"fusion.225 bf16[65536,8,128]". A `while` (the scan over layers) is an
event that covers the events of its body, so control-flow operations are
left out of busy time and of the ranking. Host threads are lines of the
plane "/host:CPU"; with Python tracing on they also hold one event for
every Python call ("$engine.py:1543 _admit").

Reduced here, per device and then averaged over the devices used:
  busy_s, window_s   union of the op intervals; the traced window is the
                     span from the first to the last device event
  modules            per jitted function: count, median / mean / total s
  programs           the same per compiled program (function and id)
  ops                per (program, op): total seconds
  collective_s       time inside all-reduce / all-gather / reduce-scatter /
                     all-to-all / collective-permute ops
  idle_gaps          the device's idle gaps, each charged to the host event
                     that covers most of it (what the host was doing)
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I,
)
MODULE_ID_RE = re.compile(r"\(\d+\)$")
OP_RE = re.compile(r"^%?([^\s=]+) = \(?([A-Za-z0-9]+\[[0-9,]*\])?")
CONTROL_FLOW = ("while", "conditional", "call")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_dir(trace_dir: str) -> Optional[Dict[str, Any]]:
    path = find_xplane(trace_dir)
    return reduce_file(path) if path else None


def reduce_file(path: str) -> Optional[Dict[str, Any]]:
    from jax.profiler import ProfileData

    return reduce_planes(load_planes(ProfileData.from_file(path)))


def load_planes(pd) -> List[Dict[str, Any]]:
    """ProfileData -> plain lists: [{"name", "lines": [{"name", "events":
    [(name, start_ns, duration_ns)]}]}] (what a recorded trace in the
    tests is kept as)."""
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            lines.append({"name": line.name, "events": evs})
        out.append({"name": plane.name, "lines": lines})
    return out


def _union(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total covered length and the merged intervals."""
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return sum(b - a for a, b in merged), merged


def _module_name(name: str) -> str:
    return MODULE_ID_RE.sub("", name)


def short_op(name: str) -> str:
    """"%fusion.225 = bf16[65536,8,128]{2,1,0:T(8,128)} fusion(...)" ->
    "fusion.225 bf16[65536,8,128]"; a name of another form is cut to 96."""
    m = OP_RE.match(name)
    if not m:
        return name[:96]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:96]


def _is_control_flow(name: str) -> bool:
    m = OP_RE.match(name)
    op = (m.group(1) if m else name).lstrip("%")
    return op.split(".")[0] in CONTROL_FLOW


def reduce_planes(planes: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    hosts = [p for p in planes if p["name"].startswith("/host:")]
    per_dev = []
    for p in devices:
        ops = [e for l in p["lines"] if l["name"] in OP_LINES
               for e in l["events"] if not _is_control_flow(e[0])]
        mods = [e for l in p["lines"] if l["name"] in MODULE_LINES
                for e in l["events"]]
        if not ops:
            continue
        per_dev.append(_reduce_device(ops, mods, hosts))
    if not per_dev:
        return None
    n = len(per_dev)
    out: Dict[str, Any] = {
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "window_s": sum(d["window_s"] for d in per_dev) / n,
        "collective_s": sum(d["collective_s"] for d in per_dev) / n,
    }
    # programs and ops: of the first device (all devices run one program)
    out["modules"] = per_dev[0]["modules"]
    out["programs"] = per_dev[0]["programs"]
    out["top_ops"] = per_dev[0]["top_ops"]
    out["idle_gaps"] = per_dev[0]["idle_gaps"]
    return out


def _reduce_device(ops, mods, hosts) -> Dict[str, Any]:
    ns = 1e-9
    t_first = min(s for _, s, _ in ops)
    t_last = max(s + d for _, s, d in ops)
    busy_ns, merged = _union([(s, s + d) for _, s, d in ops])
    # which program each op ran in: the module event that covers its start
    mods_sorted = sorted(mods, key=lambda e: e[1])
    modules: Dict[str, List[float]] = {}
    programs: Dict[str, List[float]] = {}
    for name, _, d in mods_sorted:
        modules.setdefault(_module_name(name), []).append(d * ns)
        programs.setdefault(name, []).append(d * ns)
    op_time: Dict[str, float] = {}
    coll_ns = 0.0
    starts = [m[1] for m in mods_sorted]
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = ""
        if i >= 0 and s < mods_sorted[i][1] + mods_sorted[i][2]:
            prog = _module_name(mods_sorted[i][0])
        key = f"{prog}/{short_op(name)}" if prog else short_op(name)
        op_time[key] = op_time.get(key, 0.0) + d * ns
        if COLLECTIVE_RE.search(name):
            coll_ns += d
    top = sorted(op_time.items(), key=lambda kv: -kv[1])
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]

    def stats(groups):
        return {
            k: {"count": len(v), "median_s": statistics.median(v),
                "mean_s": sum(v) / len(v), "total_s": sum(v)}
            for k, v in groups.items()
        }

    return {
        "busy_s": busy_ns * ns,
        "window_s": (t_last - t_first) * ns,
        "collective_s": coll_ns * ns,
        "modules": stats(modules),
        "programs": stats(programs),
        "top_ops": [[k, v] for k, v in top[:10]],
        "idle_gaps": _attribute_gaps(gaps, hosts),
    }


def _attribute_gaps(gaps, hosts, min_ns: float = 20_000.0) -> List[List[Any]]:
    """Charge every idle gap of at least 20 us to the host event that
    overlaps it most; sum by that event's name."""
    host_events = []
    for p in hosts:
        for l in p["lines"]:
            # runtime events, the harness's annotations, and Python calls
            # that name their file; not every builtin a thread touched
            host_events.extend(
                e for e in l["events"]
                if e[2] > 0 and (not e[0].startswith("$") or ".py:" in e[0]))
    host_events.sort(key=lambda e: e[1])
    starts = [e[1] for e in host_events]
    longest = max((e[2] for e in host_events), default=0.0)
    by_name: Dict[str, float] = {}
    for a, b in gaps:
        if b - a < min_ns:
            continue
        best, best_ov = "", 0.0
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        for name, s, d in host_events[lo:hi]:
            ov = min(b, s + d) - max(a, s)
            # the event that covers most of the gap; of several that cover
            # it whole, the innermost (it started last)
            if ov >= best_ov and ov > 0:
                best, best_ov = name, ov
        by_name[best] = by_name.get(best, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])][:10]
