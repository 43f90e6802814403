"""trace_reduce.py against a small recorded trace (0.2 s of the
mistral-7b.longdoc cell on one v5e chip, PR 23's own chip run, gzipped)
and against synthetic planes."""
import gzip
import os
import shutil

import pytest

from benchmarks.harness import manifest as M
from benchmarks.harness import trace_reduce as TR

RECORDED = os.path.join(os.path.dirname(__file__),
                        "trace_mistral7b_longdoc_0.2s.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(RECORDED, "rb") as src, open(d / "vm.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return TR.reduce_dir(str(d.parents[2]))


def test_recorded_trace_busy_and_window(recorded):
    assert recorded["devices"] == 1
    assert recorded["busy_s"] == pytest.approx(0.20082611, rel=1e-6)
    assert recorded["window_s"] == pytest.approx(0.200849514, rel=1e-6)
    assert 0 < recorded["busy_s"] <= recorded["window_s"]
    assert recorded["collective_s"] == 0.0  # one chip: no collective


def test_recorded_trace_programs(recorded):
    dec = recorded["modules"]["jit_decode"]
    assert dec["count"] == 3
    assert dec["median_s"] == pytest.approx(0.067632508, rel=1e-6)
    assert dec["total_s"] == pytest.approx(0.200839986, rel=1e-6)
    # a compiled program keeps its id; the jitted function's name is shared
    assert [k for k in recorded["programs"] if k.startswith("jit_decode(")]


def test_recorded_trace_ops_under_the_traces_names(recorded):
    ops = dict(map(tuple, recorded["top_ops"]))
    assert len(recorded["top_ops"]) == 10
    # the gather of max_batch x max_seq_len tokens, and the whole-pool copy
    assert ops["jit_decode/fusion.225 bf16[65536,8,128]"] == pytest.approx(
        0.035982319, rel=1e-6)
    assert ops["jit_decode/copy.106 bf16[32,1921,16,8,128]"] == pytest.approx(
        0.011856961, rel=1e-6)
    assert not any("while" in k for k in ops)  # the scan's body, not the scan
    times = [v for _, v in recorded["top_ops"]]
    assert times == sorted(times, reverse=True)


@pytest.mark.parametrize("metric,want", [
    ("decode_step_ms", 67.632508),
    ("device_idle_share", 100.0 * (1 - 0.20082611 / 0.200849514)),
    ("collective_share", 0.0),
])
def test_layer_metric_readers_on_the_recorded_trace(recorded, metric, want):
    got = M.layer_reader(metric)({"trace": recorded})
    assert got == pytest.approx(want, rel=1e-5, abs=1e-9)


@pytest.mark.parametrize("metric", [
    "decode_step_ms", "prefill_chunk_ms", "device_idle_share",
    "collective_share", "decode_hbm_share", "prefill_mxu_share"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    cfg = M.config_of(M.load(), "mistral-7b-instruct-v0.2")
    run = {"trace": None, "rehearse": False, "records": [], "w0": 0, "w1": 1,
           "traced": (0, 1), "config": cfg, "family": M.family_of(cfg)}
    assert M.layer_reader(metric)(run) is None


@pytest.mark.parametrize("name,want", [
    ("%fusion.225 = bf16[65536,8,128]{2,1,0:T(8,128)(2,1)} fusion(bf16[1] %x)",
     "fusion.225 bf16[65536,8,128]"),
    ("%while.7 = (s32[]{:T(128)}, bf16[8,1,4096]{2,0,1}) while(...)", "while.7 s32[]"),
    ("%all-reduce.3 = f32[32,4096]{1,0} all-reduce(f32[32,4096] %y)",
     "all-reduce.3 f32[32,4096]"),
    ("not an hlo line", "not an hlo line"),
])
def test_short_op(name, want):
    assert TR.short_op(name) == want


def _plane(name, **lines):
    return {"name": name, "lines": [
        {"name": k.replace("_", " "), "events": v} for k, v in lines.items()]}


def test_busy_is_the_union_without_control_flow():
    dev = _plane(
        "/device:TPU:0",
        XLA_Modules=[("jit_decode(1)", 0.0, 100.0), ("jit_decode(1)", 200.0, 100.0),
                     ("jit_other(2)", 400.0, 50.0)],
        XLA_Ops=[("%while.1 = () while()", 0.0, 450.0),  # covers its body
                 ("%fusion.1 = f32[4]{0} fusion()", 0.0, 60.0),
                 ("%fusion.2 = f32[4]{0} fusion()", 40.0, 60.0),  # overlaps
                 ("%fusion.1 = f32[4]{0} fusion()", 200.0, 100.0),
                 ("%all-reduce.9 = f32[4]{0} all-reduce()", 400.0, 50.0)],
    )
    r = TR.reduce_planes([dev])
    assert r["busy_s"] == pytest.approx(250e-9)       # 100 + 100 + 50
    assert r["window_s"] == pytest.approx(450e-9)
    assert r["collective_s"] == pytest.approx(50e-9)
    assert r["modules"]["jit_decode"]["count"] == 2
    assert r["modules"]["jit_decode"]["median_s"] == pytest.approx(100e-9)
    ops = dict(map(tuple, r["top_ops"]))
    assert ops["jit_decode/fusion.1 f32[4]"] == pytest.approx(160e-9)
    assert ops["jit_other/all-reduce.9 f32[4]"] == pytest.approx(50e-9)


def test_idle_gaps_are_charged_to_what_the_host_was_doing():
    dev = _plane(
        "/device:TPU:0",
        XLA_Modules=[("jit_decode(1)", 0.0, 1e6)],
        XLA_Ops=[("%fusion.1 = f32[4]{0} fusion()", 0.0, 1e5),
                 ("%fusion.1 = f32[4]{0} fusion()", 4e5, 1e5),    # gap 3e5
                 ("%fusion.1 = f32[4]{0} fusion()", 5.1e5, 1e5),  # gap 1e4: short
                 ("%fusion.1 = f32[4]{0} fusion()", 9e5, 1e5)],   # gap 2.9e5
    )
    host = _plane(
        "/host:CPU",
        python3=[("bench.submit", 1.2e5, 2.6e5), ("$builtins len", 1.3e5, 1e3),
                 ("$engine.py:1543 _admit", 6.2e5, 2.7e5)],
    )
    r = TR.reduce_planes([dev, host])
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == {"bench.submit": pytest.approx(3e-4),
                    "$engine.py:1543 _admit": pytest.approx(2.9e-4)}
    assert r["busy_s"] / r["window_s"] == pytest.approx(0.4)


def test_several_devices_are_averaged_and_no_device_is_nothing():
    def dev(i, busy):
        return _plane(f"/device:TPU:{i}",
                      XLA_Modules=[("jit_decode(1)", 0.0, 100.0)],
                      XLA_Ops=[("%fusion.1 = f32[4]{0} fusion()", 0.0, busy),
                               ("%fusion.1 = f32[4]{0} fusion()", 90.0, 10.0)])

    r = TR.reduce_planes([dev(0, 50.0), dev(1, 70.0), _plane("/host:CPU")])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(70e-9)
    assert TR.reduce_planes([_plane("/host:CPU", python3=[("x", 0.0, 1.0)])]) is None
    assert TR.reduce_dir("/nonexistent-trace-dir") is None
