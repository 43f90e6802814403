"""trace_scopes.py: the wire-format reader against a hand-encoded XSpace
and the two recorded chip traces, device time by region, scheduler time by
phase, and the readers built on them."""
import gzip
import json
import os
import shutil
import struct
import subprocess
import sys

import pytest

from benchmarks.harness import manifest as M
from benchmarks.harness import trace_scopes as TS

HERE = os.path.dirname(__file__)
OLD = os.path.join(HERE, "trace_mistral7b_longdoc_0.2s.xplane.pb.gz")
NEW = os.path.join(HERE, "trace_mistral7b_chat_0.3s_scopes.xplane.pb.gz")
NEW_METRICS = [
    "decode_kv_gather_ms", "decode_kv_carry_ms", "decode_attn_ms",
    "decode_matmul_ms", "decode_weights_hbm_share", "decode_unscoped_share",
    "prefill_attn_ms", "prefill_attn_ms.tok", "sched_host_work_ms",
    "kv_pages_live_share"]
CFG = M.config_of(M.load(), "mistral-7b-instruct-v0.2")
FAMILY = M.family_of(CFG)


# -- the wire format: a tiny XSpace encoded by hand ------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field, value):
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value & (2**64 - 1))
    if isinstance(value, float):
        return _varint(field << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _xspace():
    stat_meta = {1: "tf_op", 2: "flops", 3: "bytes_accessed", 4: "other",
                 5: "jit(decode)/layers/while/body/kv.gather/gather:"}
    ev_meta = {
        7: ("%fusion.9 = bf16[8,128]{1,0} fusion(bf16[8] %p)",
            [_f(1, 1) + _f(7, 5), _f(1, 2) + _f(3, 300), _f(1, 3) + _f(4, 4096),
             _f(1, 4) + _f(2, 0.5)]),
        8: ("jit_decode(42)", []),
        300: ("%copy.1 = bf16[8]{0} copy(bf16[8] %q)",
              [_f(1, 1) + _f(5, "jit(decode)/layers/while:")]),
    }
    plane = _f(1, 2) + _f(2, "/device:TPU:0")
    for k, name in stat_meta.items():
        plane += _f(5, _f(1, k) + _f(2, _f(1, k) + _f(2, name)))
    for k, (name, stats) in ev_meta.items():
        md = _f(1, k) + _f(2, name) + b"".join(_f(5, s) for s in stats)
        plane += _f(4, _f(1, k) + _f(2, md))
    ops = (_f(1, 3) + _f(2, "XLA Ops") + _f(3, 1000)
           + _f(4, _f(1, 7) + _f(2, 5_000_000) + _f(3, 2_000_000))
           + _f(4, _f(1, 300) + _f(2, 8_000_000) + _f(3, 500_000)))
    mods = (_f(1, 2) + _f(2, "XLA Modules") + _f(3, 1000)
            + _f(4, _f(1, 8) + _f(2, 4_000_000) + _f(3, 5_000_000)))
    return _f(1, plane + _f(3, mods) + _f(3, ops)) + _f(4, "hostname")


def test_wire_reader_reads_planes_lines_events_and_metadata_stats():
    (plane,) = TS.load_xspace(_xspace())
    assert plane["name"] == "/device:TPU:0"
    assert [l["name"] for l in plane["lines"]] == ["XLA Modules", "XLA Ops"]
    # times: the line's timestamp_ns plus the event's offset_ps
    assert plane["lines"][1]["events"] == [(7, 6000.0, 2000.0),
                                           (300, 9000.0, 500.0)]
    m = plane["meta"][7]
    assert m["name"].startswith("%fusion.9 = bf16[8,128]")
    assert m["tf_op"] == "jit(decode)/layers/while/body/kv.gather/gather:"  # a ref
    assert m["flops"] == 300 and m["bytes_accessed"] == 4096
    assert "other" not in m  # only the stats that were asked for
    assert plane["meta"][300]["tf_op"] == "jit(decode)/layers/while:"
    red = TS.reduce_planes([plane])
    p = red["programs"]["jit_decode(42)"]
    assert p["executions"] == 1 and p["device_ms"] == pytest.approx(5e-3)
    assert p["scopes"]["kv.gather"] == {"ms": pytest.approx(2e-3),
                                        "bytes": 4096, "flops": 300}
    assert p["scopes"]["layers"]["ms"] == pytest.approx(5e-4)
    assert red["host"] is None


@pytest.mark.parametrize("tf_op,want", [
    ("jit(decode)/layers/while/body/closed_call/attn.qkv/bsd,dhk->bshk/dot_general:",
     "attn.qkv"),
    ("jit(decode)/layers/while/body/dynamic_slice:", "layers"),
    ("jit(decode)/layers/while:", "layers"),
    ("jit(decode)/sample/jit(_where)/select_n:", "sample"),
    ("jit(_chunk_prefill_jit)/layers/while/body/closed_call/kv.gather/gather:",
     "kv.gather"),
    ("jit(decode)/while/body/closed_call/bsd,dhk->bshk/dot_general:", "unscoped"),
    ("jit(decode)/layers/while/body/closed_call/my.attn.core/x:", "layers"),
    ("", "unscoped"),
])
def test_an_op_is_charged_to_the_innermost_vocabulary_name(tf_op, want):
    assert TS.scope_of(tf_op) == want


# -- the recorded traces ----------------------------------------------------------

def test_old_trace_without_names_is_all_unscoped_and_readers_return_nothing():
    """PR 23's trace: the parent's program, no region and no phase."""
    red = TS.reduce_planes(TS.load_file(OLD))
    p = TS.program(red, TS.DECODE)
    # three executions on the line, the first and the last cut by the
    # capture's edges (53.8 and 67.6 ms: trace_reduce's median is the cut
    # 67.6); the whole one
    assert p["executions"] == 1
    assert p["device_ms"] == pytest.approx(79.4464025, rel=1e-6)
    assert set(p["scopes"]) == {TS.UNSCOPED}
    assert p["scopes"][TS.UNSCOPED]["ms"] == pytest.approx(p["ops_ms"])
    assert red["host"] is None
    assert TS.scope_ms(red, TS.DECODE, ("kv.gather",)) is None
    assert TS.host_work_ms(red) is None
    assert TS.program(red, TS.CHUNK) is None


@pytest.fixture(scope="module")
def recorded():
    return TS.reduce_planes(TS.load_file(NEW))


@pytest.mark.parametrize("function", [TS.DECODE, TS.CHUNK])
def test_scope_time_adds_up_to_the_programs_device_time(recorded, function):
    """Scope seconds plus `unscoped` against the program's own event on the
    "XLA Modules" line, to 1 %."""
    p = TS.program(recorded, function)
    assert p is not None and p["executions"] >= 1
    total = sum(v["ms"] for v in p["scopes"].values())
    assert total == pytest.approx(p["device_ms"], rel=0.01)
    assert set(p["scopes"]) - {TS.UNSCOPED} <= set(TS.SCOPES)
    # what no scope reaches: the two copies of the whole KV pool that the
    # compiler inserts ahead of the scan, which carry no name stack at all
    unscoped = p["scopes"][TS.UNSCOPED]["ms"]
    copies = sum(ms for sc, op, ms in p["top_ops"] if sc == TS.UNSCOPED
                 and op.startswith("copy.") and "[32,1793,16,8,128]" in op)
    assert copies > 0.95 * unscoped and unscoped < 0.2 * max(total, 60.0)


def test_recorded_trace_regions_phases_and_readers(recorded, tmp_path,
                                                   monkeypatch):
    dec = TS.program(recorded, TS.DECODE)
    for name in ("kv.gather", "kv.write", "layers", "attn.core", "attn.qkv",
                 "attn.out", "mlp", "lm_head", "norm", "sample", "embed"):
        assert name in dec["scopes"], name
    # the pool-sized gather is the largest region of a step, as PERF.md
    # section 5 says, and XLA's byte count for it is of the gathered size
    gather = dec["scopes"]["kv.gather"]
    assert gather["ms"] == max(v["ms"] for v in dec["scopes"].values())
    assert gather["bytes"] > 32 * 2 * 32 * 2048 * 8 * 128 * 2  # K and V, 32 layers
    host = recorded["host"]
    assert host["covered_share"] > 0.98
    assert sum(host["idle_gaps_ms"].values()) < 0.03 * host["thread_ms"]
    assert all(k.startswith("engine.") or k == "(no engine phase)"
               for k in host["idle_gaps_ms"])
    # the readers, through the run's trace directory
    d = tmp_path / ".bench_out" / "trace" / "cellname" / "plugins" / "profile" / "r"
    d.mkdir(parents=True)
    with gzip.open(NEW, "rb") as src, open(d / "vm.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.chdir(tmp_path)
    run = {"cell": {"name": "cellname"}, "rehearse": False, "family": FAMILY}
    got = {m: M.layer_reader(m)(run) for m in NEW_METRICS[:4] + NEW_METRICS[5:9]}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["decode_kv_gather_ms"] == pytest.approx(gather["ms"])
    assert got["decode_kv_carry_ms"] == pytest.approx(
        dec["scopes"]["kv.write"]["ms"] + dec["scopes"]["layers"]["ms"])
    parts = (got["decode_kv_gather_ms"] + got["decode_kv_carry_ms"]
             + got["decode_attn_ms"] + got["decode_matmul_ms"])
    assert 0.8 * dec["device_ms"] < parts < dec["device_ms"]
    assert got["prefill_attn_ms"] == got["prefill_attn_ms.tok"]
    assert 10.0 < got["decode_unscoped_share"] < 16.0  # the two pool copies
    assert 0.05 < got["sched_host_work_ms"] < 20.0


# -- the host side on synthetic planes --------------------------------------------

def _host_plane(events):
    names = sorted({e[0] for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    return {"name": "/host:CPU", "meta": {i: {"name": n} for n, i in ids.items()},
            "lines": [{"name": "python3", "events": [
                (ids[n], s, d) for n, s, d in events]},
                {"name": "other", "events": []}]}


def _dev_plane(ops, mods):
    meta = {1: {"name": "%fusion.1 = f32[4]{0} fusion()", "tf_op": "jit(decode)/mlp/x:"},
            2: {"name": "jit_decode(1)"}}
    return {"name": "/device:TPU:0", "meta": meta, "lines": [
        {"name": "XLA Ops", "events": [(1, s, d) for s, d in ops]},
        {"name": "XLA Modules", "events": [(2, s, d) for s, d in mods]}]}


def test_iteration_work_is_wall_minus_waits_and_idle_and_gaps_go_to_phases():
    host = _host_plane([
        ("engine.broadcast", 0.0, 10.0),
        ("engine.iter", 10.0, 1000.0),
        ("engine.admit", 20.0, 100.0),
        ("engine.dispatch", 130.0, 200.0),
        ("engine.drain", 340.0, 600.0),
        ("engine.wait.drain", 350.0, 500.0),
        ("engine.emit", 860.0, 70.0),
        ("engine.broadcast", 1010.0, 10.0),
        ("engine.iter", 1020.0, 980.0),       # an idle pass: no dispatch
        ("engine.admit", 1030.0, 50.0),
        ("engine.idle", 1090.0, 900.0),
        ("bench.submit", 100.0, 800.0),       # not the program's: ignored
    ])
    dev = _dev_plane(ops=[(0.0, 100.0), (100_100.0, 100.0), (250_000.0, 50.0)],
                     mods=[(0.0, 300_000.0)])
    # two idle gaps of the device: the first wholly inside the dispatch,
    # 60 % of the second inside the wait of the drain
    host2 = _host_plane([
        ("engine.iter", 0.0, 300_000.0),
        ("engine.dispatch", 50.0, 150_000.0),
        ("engine.drain", 160_000.0, 130_000.0),
        ("engine.wait.drain", 160_100.0, 120_000.0),
    ])
    red = TS.reduce_planes([dev, host])
    its = red["host"]["iters"]
    assert [i["decoded"] for i in its] == [True, False]
    assert its[0]["wall_ms"] == pytest.approx(1e-3)
    assert its[0]["wait_ms"] == pytest.approx(5e-4)
    assert its[0]["work_ms"] == pytest.approx(5e-4)
    assert its[1]["idle_ms"] == pytest.approx(9e-4)
    assert its[1]["work_ms"] == pytest.approx(8e-5)
    assert TS.host_work_ms(red) == pytest.approx(5e-4)  # decoding passes only
    assert red["host"]["covered_share"] == pytest.approx(1.0)
    red2 = TS.reduce_planes([dev, host2])
    gaps = red2["host"]["idle_gaps_ms"]
    assert gaps == {"engine.wait.drain": pytest.approx(0.1498),
                    "engine.dispatch": pytest.approx(0.1)}
    assert TS.reduce_planes([dev])["host"] is None  # no phase: nothing


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_with_nothing_to_read_returns_nothing(metric, tmp_path,
                                                           monkeypatch):
    """The parent's program has no region, no phase and no live-page sums:
    the line leaves the metric out and nothing raises."""
    monkeypatch.chdir(tmp_path)  # no .bench_out here
    run = {"cell": {"name": "mistral-7b.chat"}, "rehearse": False,
           "records": [], "traced": (0.0, 1.0), "chips": 1,
           "counters": {"stats": {"preemptions": 0}},
           "config": CFG, "family": FAMILY,
           "device": {"kind": "TPU v5 lite"}}
    assert M.layer_reader(metric)(run) is None
    # and with the parent's trace: every op unscoped, no engine.* span
    d = tmp_path / ".bench_out" / "trace" / "mistral-7b.chat" / "plugins" / "profile" / "r"
    d.mkdir(parents=True)
    with gzip.open(OLD, "rb") as src, open(d / "vm.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    assert M.layer_reader(metric)(run) is None


def test_weights_share_counts_each_matmul_weight_once(monkeypatch):
    import types

    from benchmarks.harness import counts

    cfg = CFG
    need = sum(b for n, b in counts.weight_bytes(FAMILY.leaf_table(cfg)).items()
               if n.split("/")[-1] not in ("tok_embed", "attn_norm", "mlp_norm"))
    assert need == FAMILY.decode_matmul_weight_bytes(cfg, 1)
    assert 7.0e9 < need < 7.3e9  # 7.24 B parameters less the embedding, int8
    read = M.layer_reader("decode_weights_hbm_share")
    real = M.layer_reader
    monkeypatch.setattr(  # a 10 ms matmul phase on one v5e
        M, "layer_reader", lambda name, root=M.ROOT: (lambda run: 10.0)
        if name == "decode_matmul_ms" else real(name, root))
    decoding = types.SimpleNamespace(first=0.1, done=None)
    run = {"rehearse": False, "records": [decoding], "traced": (0.0, 1.0),
           "config": cfg, "family": FAMILY,
           "device": {"kind": "TPU v5 lite"}, "chips": 1}
    assert read(run) == pytest.approx(100.0 * need / (10e-3 * 819e9))
    assert read(dict(run, records=[])) is None  # nobody decoding: no step
    assert read(dict(run, rehearse=True)) is None


# -- the rehearsal reports the program-side metrics --------------------------------

@pytest.mark.parametrize("cell,want", [
    ("mistral-7b.chat", {"sched_host_work_ms"}),
    # not sched_host_work_ms: one admission of a long prompt is an iteration
    # of up to 2 s, and 3 s of trace may hold no whole one (PERF.md section 3)
    ("mistral-7b.longdoc", {"kv_pages_live_share"}),
])
def test_rehearsal_reports_host_work_and_live_pages(cell, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", cell, "--seed",
         str(2**31 + 24), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=M.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    per_layer = line["counts"]["per_layer"]
    assert want <= set(per_layer)
    if "sched_host_work_ms" in want:
        assert 0 < per_layer["sched_host_work_ms"]["value"] < 1000
    if "kv_pages_live_share" in want:
        assert 0 < per_layer["kv_pages_live_share"]["value"] <= 100
    # a CPU run writes nothing under a device metric's name
    assert not [m for m in per_layer if m.startswith(("decode_", "prefill_"))]
    assert line["metrics"] == {}
