"""A traced run by the program's own names: device time per model region
and scheduler time per phase.

The program names its regions with `jax.named_scope` (a closed vocabulary:
the base names copied below, and what a model family's block adds to them,
listed as `SCOPES` in its `benchmarks/families/<family>.py`) and its
scheduler phases with profiler annotations "engine.<phase>". Both land in
the profiler's `.xplane.pb`:

  * every event on the TPU plane's "XLA Ops" line points at an event
    metadata whose *stats* hold the JAX name stack (`tf_op`:
    "jit(decode)/layers/while/body/closed_call/attn.qkv/bsd,dhk->bshk/
    dot_general:"), XLA's own cost numbers (`flops`, `bytes_accessed`) and
    the `program_id`. `jax.profiler.ProfileData` (jax 0.9) exposes an
    event's own stats only, not its metadata's, so the file is read here
    with a small decoder of the protobuf wire format: the dozen fields of
    XSpace / XPlane / XLine / XEvent / XEventMetadata / XStat that are
    needed, and nothing to install;
  * annotations are plain events on a thread's line of the "/host:CPU"
    plane, on the same time base as the device's lines.

`read(trace_dir)` reduces the newest trace under a directory once a
process and keeps the result; a trace of a program without the names (the
parent of the PR that added them) gives everything as `unscoped` and no
iterations, and a reader built on this returns nothing. Imports nothing
from the program.

    python3 -m benchmarks.harness.trace_scopes <trace dir or .xplane.pb[.gz]>

prints the tables PERF.md section 5 is made of.
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import os
import statistics
import struct
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmarks.harness import manifest
from benchmarks.harness.trace_reduce import (
    MODULE_LINES, OP_LINES, _is_control_flow, _module_name, _union,
    find_xplane, short_op,
)

# substratus_tpu/ops/scopes.py, copied: the benchmark keeps its own. The
# base vocabulary; `vocabulary()` adds the family files' own regions.
SCOPES = (
    "embed", "layers", "norm", "attn.qkv", "kv.write", "kv.gather",
    "attn.core", "attn.out", "mlp", "moe.router", "moe.experts", "lm_head",
    "sample",
)
UNSCOPED = "unscoped"
DECODE = "jit_decode"
CHUNK = "jit__chunk_prefill_jit"
ITER, BROADCAST, DISPATCH, IDLE = (
    "engine.iter", "engine.broadcast", "engine.dispatch", "engine.idle")
WAIT = "engine.wait."
MIN_GAP_NS = 20_000.0
TRACE_ROOT = os.path.join(".bench_out", "trace")  # benchmarks/run.py OUT_DIR


# -- the wire format -----------------------------------------------------------

def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, pos
        shift += 7


def _fields(buf: bytes, pos: int = 0, end: Optional[int] = None
            ) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: an int for varint
    and fixed fields, a (start, end) pair into `buf` for length-delimited
    ones (nothing is copied until a caller wants the bytes)."""
    end = len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
            yield key >> 3, 0, val
        elif wire == 2:
            n, pos = _varint(buf, pos)
            yield key >> 3, 2, (pos, pos + n)
            pos += n
        elif wire == 1:
            yield key >> 3, 1, struct.unpack_from("<Q", buf, pos)[0]
            pos += 8
        elif wire == 5:
            yield key >> 3, 5, struct.unpack_from("<I", buf, pos)[0]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, span: Tuple[int, int]) -> Tuple[int, Any]:
    """XStat -> (stat metadata id, value); a ref_value stays ("ref", id)."""
    mid, val = 0, None
    for f, w, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f in (5, 6):
            val = _text(buf, v)
        elif f == 7:
            val = ("ref", v)
    return mid, val


def _map_entry(buf: bytes, span: Tuple[int, int]) -> Tuple[int, Tuple[int, int]]:
    key, val = 0, (0, 0)
    for f, w, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def load_xspace(data: bytes,
                want_meta_stats=("tf_op", "flops", "bytes_accessed")
                ) -> List[Dict[str, Any]]:
    """The planes of a serialized XSpace: [{"name", "lines": [{"name",
    "events": [(metadata id, start_ns, duration_ns)]}], "meta": {id:
    {"name", + the wanted metadata stats}}}]. Times are on one base over
    all planes: a line's timestamp_ns plus the event's offset_ps."""
    planes = []
    for f, w, v in _fields(data):
        if f != 1 or w != 2:
            continue
        name, lines, emeta, smeta = "", [], [], {}
        for pf, pw, pv in _fields(data, *v):
            if pf == 2:
                name = _text(data, pv)
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:
                emeta.append(pv)
            elif pf == 5:
                k, span = _map_entry(data, pv)
                for sf, sw, sv in _fields(data, *span):
                    if sf == 2:
                        smeta[k] = _text(data, sv)
        wanted = {k for k, n in smeta.items() if n in want_meta_stats}
        meta: Dict[int, Dict[str, Any]] = {}
        for span in emeta:
            k, mspan = _map_entry(data, span)
            m: Dict[str, Any] = {"name": ""}
            for mf, mw, mv in _fields(data, *mspan):
                if mf == 2:
                    m["name"] = _text(data, mv)
                elif mf == 5 and wanted:
                    sid, val = _stat(data, mv)
                    if sid in wanted:
                        if isinstance(val, tuple):
                            val = smeta.get(val[1], "")
                        m[smeta[sid]] = val
            meta[k] = m
        out_lines = []
        for span in lines:
            lname, t0, events = "", 0, []
            for lf, lw, lv in _fields(data, *span):
                if lf == 2:
                    lname = _text(data, lv)
                elif lf == 3:
                    t0 = _signed(lv)
                elif lf == 4:
                    mid = off = dur = 0
                    for ef, ew, ev in _fields(data, *lv):
                        if ef == 1:
                            mid = ev
                        elif ef == 2:
                            off = ev
                        elif ef == 3:
                            dur = ev
                    events.append((mid, off, dur))
            out_lines.append({"name": lname, "events": [
                (mid, t0 + off * 1e-3, dur * 1e-3) for mid, off, dur in events]})
        planes.append({"name": name, "lines": out_lines, "meta": meta})
    return planes


def load_file(path: str) -> List[Dict[str, Any]]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return load_xspace(f.read())


# -- device side: time by region -------------------------------------------------

@functools.lru_cache(maxsize=None)
def vocabulary() -> frozenset:
    """The base names and the `SCOPES` of every family file present."""
    return frozenset(SCOPES).union(
        *(getattr(f, "SCOPES", ()) for f in manifest.families()))


def scope_of(tf_op: str, scopes=None) -> str:
    """The innermost vocabulary name on an op's JAX name stack."""
    scopes = vocabulary() if scopes is None else scopes
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def _device(plane: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    meta = plane["meta"]
    scopes = vocabulary()
    ops = [e for l in plane["lines"] if l["name"] in OP_LINES
           for e in l["events"]
           if not _is_control_flow(meta[e[0]]["name"])]
    mods = sorted((e for l in plane["lines"] if l["name"] in MODULE_LINES
                   for e in l["events"]), key=lambda e: e[1])
    if not ops or not mods:
        return None
    starts = [m[1] for m in mods]
    # one row per execution of a program: [scope -> ns], bytes, flops
    runs: List[Dict[str, Any]] = [
        {"program": meta[m[0]]["name"], "wall_ns": m[2], "ns": {},
         "bytes": {}, "flops": {}, "ops": {}} for m in mods]
    for mid, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][1] + mods[i][2]:
            continue
        m = meta[mid]
        sc = scope_of(str(m.get("tf_op", "")), scopes)
        r = runs[i]
        r["ns"][sc] = r["ns"].get(sc, 0.0) + d
        r["bytes"][sc] = r["bytes"].get(sc, 0) + int(m.get("bytes_accessed", 0) or 0)
        r["flops"][sc] = r["flops"].get(sc, 0) + int(m.get("flops", 0) or 0)
        key = (sc, short_op(m["name"]))
        r["ops"][key] = r["ops"].get(key, 0.0) + d
    # The capture's edges cut the first and the last execution on the line
    # (their events start with the first traced op, end with the last): a
    # program is read from its whole executions where it has any.
    programs: Dict[str, Dict[str, Any]] = {}
    for name in sorted({r["program"] for r in runs}):
        rows = ([r for r in runs[1:-1] if r["program"] == name]
                or [r for r in runs if r["program"] == name])
        names = sorted({k for r in rows for k in r["ns"]})
        op_ns = {k: statistics.median(r["ops"].get(k, 0.0) for r in rows)
                 for k in {k for r in rows for k in r["ops"]}}
        programs[name] = {
            "executions": len(rows),
            "device_ms": statistics.median(r["wall_ns"] for r in rows) * 1e-6,
            "ops_ms": statistics.median(
                sum(r["ns"].values()) for r in rows) * 1e-6,
            "scopes": {
                sc: {
                    "ms": statistics.median(
                        r["ns"].get(sc, 0.0) for r in rows) * 1e-6,
                    "bytes": statistics.median(
                        r["bytes"].get(sc, 0) for r in rows),
                    "flops": statistics.median(
                        r["flops"].get(sc, 0) for r in rows),
                } for sc in names},
            "top_ops": [[sc, op, v * 1e-6] for (sc, op), v in
                        sorted(op_ns.items(), key=lambda kv: -kv[1])[:16]],
        }
    _, merged = _union([(s, s + d) for _, s, d in ops])
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    return {"programs": programs, "gaps": gaps}


def program(reduced: Optional[Dict[str, Any]], function: str
            ) -> Optional[Dict[str, Any]]:
    """The compiled program of a jitted function; of several (one a
    bucket), the one whose executions take longest: the full chunk."""
    progs = [(p["device_ms"], p) for n, p in
             ((reduced or {}).get("programs") or {}).items()
             if _module_name(n) == function]
    return max(progs, key=lambda x: x[0])[1] if progs else None


def named_program(reduced: Optional[Dict[str, Any]], function: str
                  ) -> Optional[Dict[str, Any]]:
    """`program`, or nothing where it carries no vocabulary name at all
    (a build from before the regions were named)."""
    p = program(reduced, function)
    return p if p is not None and set(p["scopes"]) - {UNSCOPED} else None


def scope_ms(reduced: Optional[Dict[str, Any]], function: str,
             scopes: Tuple[str, ...]) -> Optional[float]:
    """Median device ms of these regions in one execution of the function's
    program."""
    p = named_program(reduced, function)
    if p is None:
        return None
    return sum(p["scopes"].get(s, {}).get("ms", 0.0) for s in scopes)


# -- host side: time by phase ----------------------------------------------------

def _host(planes: List[Dict[str, Any]], gaps: List[Tuple[float, float]]
          ) -> Optional[Dict[str, Any]]:
    """The scheduler thread is the line that holds engine.iter events."""
    best = None
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for l in p["lines"]:
            evs = [(p["meta"][m]["name"], s, d) for m, s, d in l["events"]]
            evs = [e for e in evs if e[0].startswith("engine.")]
            n = sum(1 for e in evs if e[0] == ITER)
            if n and (best is None or n > best[0]):
                best = (n, evs)
    if best is None:
        return None
    evs = sorted(best[1], key=lambda e: (e[1], -e[2]))
    iters = []
    covered = 0.0
    children = [e for e in evs if e[0] not in (ITER, BROADCAST)]
    cstarts = [e[1] for e in children]
    for name, s, d in evs:
        if name == BROADCAST:
            covered += d
        if name != ITER:
            continue
        covered += d
        lo = bisect.bisect_left(cstarts, s)
        hi = bisect.bisect_right(cstarts, s + d)
        inside = children[lo:hi]
        # waits nest inside drain / sample / flush, never inside each other
        wait = sum(e[2] for e in inside if e[0].startswith(WAIT))
        idle = sum(e[2] for e in inside if e[0] == IDLE)
        iters.append({
            "wall_ms": d * 1e-6, "wait_ms": wait * 1e-6,
            "idle_ms": idle * 1e-6, "work_ms": (d - wait - idle) * 1e-6,
            "decoded": any(e[0] == DISPATCH for e in inside),
            "phases": _sum_by_name(inside),
        })
    first = min(e[1] for e in evs if e[0] in (ITER, BROADCAST))
    last = max(e[1] + e[2] for e in evs if e[0] in (ITER, BROADCAST))
    # every idle gap of the device, charged to the innermost engine.* span
    # that covers most of it: of the spans that cover over half the gap,
    # the one that started last (spans of one thread nest)
    starts = [e[1] for e in evs]
    longest = max(e[2] for e in evs)
    by_phase: Dict[str, float] = {}
    for a, b in gaps:
        if b - a < MIN_GAP_NS:
            continue
        name = "(no engine phase)"
        for n, s, d in evs[bisect.bisect_left(starts, a - longest):
                           bisect.bisect_right(starts, b)]:
            if min(b, s + d) - max(a, s) > 0.5 * (b - a):
                name = n
        by_phase[name] = by_phase.get(name, 0.0) + (b - a) * 1e-6
    return {
        "iters": iters,
        "thread_ms": (last - first) * 1e-6,
        "covered_share": covered / (last - first) if last > first else None,
        "idle_gaps_ms": dict(sorted(by_phase.items(), key=lambda kv: -kv[1])),
    }


def _sum_by_name(events) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, d in events:
        out[name] = out.get(name, 0.0) + d * 1e-6
    return out


def host_work_ms(reduced: Optional[Dict[str, Any]]) -> Optional[float]:
    """Median host work (iteration minus device waits minus idle) over the
    iterations that launched a decode step."""
    its = [i["work_ms"] for i in ((reduced or {}).get("host") or {}).get(
        "iters", []) if i["decoded"]]
    return statistics.median(its) if its else None


# -- one trace, reduced once -----------------------------------------------------

def reduce_planes(planes: List[Dict[str, Any]]) -> Dict[str, Any]:
    dev = None
    for p in planes:
        if p["name"].startswith("/device:TPU:") and dev is None:
            dev = _device(p)  # all chips run one program: the first
    out: Dict[str, Any] = {"programs": (dev or {}).get("programs", {})}
    out["host"] = _host(planes, (dev or {}).get("gaps", []))
    return out


_CACHE: Dict[str, Optional[Dict[str, Any]]] = {}


def read(trace_dir: str) -> Optional[Dict[str, Any]]:
    """The newest trace under a run's trace directory, reduced; None where
    there is none. Kept for the process: ten readers share one pass."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = reduce_planes(load_file(path))
    return _CACHE[path]


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """For a layer metric's read(run): benchmarks/run.py writes a cell's
    trace under .bench_out/trace/<cell name> of the working directory."""
    return read(os.path.join(TRACE_ROOT, run["cell"]["name"]))


def main(argv: List[str]) -> int:
    src = argv[1]
    red = (reduce_planes(load_file(src)) if src.endswith((".pb", ".gz"))
           else read(src))
    if red is None:
        print(f"no trace under {src}")
        return 1
    host = red["host"] or {}
    its = [i for i in host.get("iters", []) if i["decoded"]]
    print(json.dumps({
        "programs": red["programs"],
        "host": {k: v for k, v in host.items() if k != "iters"},
        "iterations": len(host.get("iters", [])),
        "decode_iterations": len(its),
        "decode_iteration_median_ms": {
            k: statistics.median(i[k] for i in its)
            for k in ("wall_ms", "wait_ms", "idle_ms", "work_ms")} if its else None,
        "decode_iteration_phase_mean_ms": {
            k: sum(i["phases"].get(k, 0.0) for i in its) / len(its)
            for k in sorted({k for i in its for k in i["phases"]})} if its else None,
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
