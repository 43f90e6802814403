"""Batch-generation engine (ISSUE 9, serve/batchgen.py): the offline
actor-gang driver must produce EXACTLY what the interactive engine
produces (greedy per-record parity is a tier-1 gate), survive a
mid-manifest SIGKILL with exactly-once output, compose with the
lockstep gang transport and multi-tenant adapters, and keep the decode
slots of two actors that drain one manifest full (>= 0.9 of the counted
slot-steps while refill is possible)."""
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.load.manifest import (
    completed_indices,
    count_records,
    iter_manifest,
    next_shard_index,
    write_manifest,
)
from substratus_tpu.models import llama
from substratus_tpu.serve.batchgen import BatchGenDriver, ProgressServer
from substratus_tpu.serve.engine import Engine, EngineConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _cfg():
    return llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)


def _engine(cfg=None, adapters=None, sync=None, max_batch=4):
    cfg = cfg or _cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    ec = EngineConfig(
        max_batch=max_batch, max_seq_len=96, eos_token_id=257,
    )
    eng = Engine(cfg, params, ec, adapters=adapters, sync=sync)
    eng.start()
    return eng


def _records(n, seed=0, prompt_len=8, lo_mt=4, hi_mt=8):
    rng = np.random.default_rng(seed)
    return [
        {
            "id": f"r{i}",
            "tokens": rng.integers(10, 250, prompt_len).tolist(),
            "max_tokens": int(rng.integers(lo_mt, hi_mt + 1)),
        }
        for i in range(n)
    ]


def _read_output(out_dir):
    got = {}
    for name in sorted(os.listdir(out_dir)):
        if not name.startswith("shard-"):
            continue
        for line in open(os.path.join(out_dir, name)):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail
            got.setdefault(rec["index"], []).append(rec)
    return got


# --- tier-1 gate: greedy per-record parity vs the interactive engine ----


def test_greedy_parity_vs_interactive_engine(tmp_path):
    """Every record generated through the manifest driver must be
    token-exact vs engine.generate() on the same prompts — the pull
    source, refill cap, and sink pipeline change scheduling, never
    sampling."""
    records = _records(10)
    man = tmp_path / "m.jsonl"
    write_manifest(str(man), records)

    ref_engine = _engine()
    try:
        want = {
            r["id"]: ref_engine.generate(
                list(r["tokens"]), max_tokens=r["max_tokens"],
                temperature=0.0,
            )
            for r in records
        }
    finally:
        ref_engine.stop()

    eng = _engine()
    try:
        summary = BatchGenDriver(
            [eng], str(man), str(tmp_path / "out")
        ).run()
    finally:
        eng.stop()
    assert summary["written"] == len(records)
    assert summary["errors"] == 0

    got = _read_output(str(tmp_path / "out"))
    assert len(got) == len(records)
    by_id = {rs[0]["id"]: rs[0] for rs in got.values()}
    for r in records:
        assert by_id[r["id"]]["tokens"] == want[r["id"]], r["id"]
        assert by_id[r["id"]]["finish_reason"] in ("stop", "length")


# --- restart/resume: kill -9 mid-manifest, rerun, exactly-once ----------


def test_restart_resume_exactly_once(tmp_path):
    """SIGKILL the driver process mid-manifest, rerun the same command:
    the union of output shards holds every manifest record EXACTLY once
    (ISSUE 9 acceptance). The output shards are the only resume state —
    parseable lines are durable, the torn tail is regenerated."""
    # Long enough a manifest that the first five records are durable
    # while most of it is still ahead.
    records = _records(120, seed=3, lo_mt=48, hi_mt=64)
    man = tmp_path / "m.jsonl"
    out = tmp_path / "out"
    write_manifest(str(man), records)

    cmd = [
        sys.executable, "-m", "substratus_tpu.serve.batchgen",
        "--manifest", str(man), "--output", str(out),
        "--config", "tiny", "--max-batch", "4", "--max-seq-len", "96",
        "--max-tokens", "8",
        "--params", str(tmp_path / "none.json"),
    ]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    # Run 1: kill -9 once a few records are durably flushed.
    p = subprocess.Popen(cmd, env=env, cwd=REPO,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 240
    try:
        while time.monotonic() < deadline:
            if p.poll() is not None:
                pytest.fail(
                    "driver finished before the kill; lengthen the manifest"
                )
            if len(completed_indices(str(out))) >= 5:
                break
            time.sleep(0.01)
        else:
            pytest.fail("driver never wrote 5 records")
        p.send_signal(signal.SIGKILL)
    finally:
        p.kill()
        p.communicate()

    first_done = completed_indices(str(out))
    assert 0 < len(first_done) < len(records), (
        "the kill must land mid-manifest for the test to mean anything"
    )

    # Run 2: same command, no kill — resumes from the shards.
    proc = subprocess.run(
        cmd, env=env, cwd=REPO, capture_output=True, text=True, timeout=240
    )
    assert proc.returncode == 0, proc.stdout[-3000:]
    summary = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1]
    )
    assert summary["resumed"] == len(first_done)
    assert summary["written"] == len(records) - len(first_done)

    got = _read_output(str(out))
    assert sorted(got) == list(range(len(records)))
    dupes = {i: rs for i, rs in got.items() if len(rs) > 1}
    assert not dupes, f"records written more than once: {sorted(dupes)}"


# --- two actors, one manifest: exactly once, slots kept full ------------


def test_two_actors_drain_one_manifest_exactly_once(tmp_path):
    """Two engines pull from one manifest cursor: every record is written
    once, each actor served a share, and while refill was possible each
    actor's decode slots stayed full: counted from the slot-steps of its
    timeline (a freed slot is refilled in the iteration that freed it),
    not from a clock."""
    # Varied budgets stagger completions, so refill is a steady drip.
    records = _records(40, seed=5, lo_mt=8, hi_mt=16)
    man = tmp_path / "m.jsonl"
    write_manifest(str(man), records)

    engines = [_engine(), _engine()]
    try:
        for eng in engines:
            eng.generate([10] * 8, max_tokens=2)  # compile before the run
        warm = [len(eng.timeline.records()) for eng in engines]
        summary = BatchGenDriver(
            engines, str(man), str(tmp_path / "out")
        ).run()
    finally:
        for eng in engines:
            eng.stop()
    assert summary["written"] == len(records) and summary["errors"] == 0

    got = _read_output(str(tmp_path / "out"))
    assert sorted(got) == list(range(len(records)))
    assert all(len(rs) == 1 for rs in got.values())

    boarded, slot_steps, steps = [], 0, 0
    for eng, skip in zip(engines, warm):
        recs = eng.timeline.records()[skip:]
        boarded.append(sum(r["admitted"] for r in recs))
        # Steady state: from the iteration that first filled the batch
        # to the last that boarded anyone (after it the batch can only
        # drain).
        full = next(i for i, r in enumerate(recs)
                    if r["active_slots"] == eng.ec.max_batch)
        last = max(i for i, r in enumerate(recs) if r["admitted"])
        steady = recs[full:last + 1]
        slot_steps += sum(r["active_slots"] for r in steady)
        steps += len(steady) * eng.ec.max_batch
    assert sum(boarded) == len(records) and min(boarded) > 0, boarded
    assert steps > 0 and slot_steps / steps >= 0.9, (slot_steps, steps)


# --- lockstep gang composition (TcpSync, the CPU transport) -------------


def test_lockstep_gang_leader_pulls_broadcast(tmp_path):
    """A 2-process-shaped lockstep gang (TcpSync over two threads — the
    transport `--transport tcp` gang benches use) driven by the batch
    source: the leader's pulls ride the event broadcast, the follower
    mirrors every admission, and output is token-exact vs the single
    engine."""
    import threading

    import socket as socket_mod

    from substratus_tpu.serve.multihost import NullSink, TcpSync

    records = _records(6, seed=7)
    man = tmp_path / "m.jsonl"
    write_manifest(str(man), records)

    ref_engine = _engine()
    try:
        want = {
            r["id"]: ref_engine.generate(
                list(r["tokens"]), max_tokens=r["max_tokens"],
                temperature=0.0,
            )
            for r in records
        }
    finally:
        ref_engine.stop()

    with socket_mod.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    syncs = {}

    def make_leader():
        syncs["leader"] = TcpSync(0, 2, port)

    t = threading.Thread(target=make_leader)
    t.start()
    syncs["follower"] = TcpSync(1, 2, port)
    t.join(timeout=30)

    leader = _engine(sync=syncs["leader"])
    follower = _engine(sync=syncs["follower"])
    try:
        summary = BatchGenDriver(
            [leader], str(man), str(tmp_path / "out")
        ).run()
        assert summary["written"] == len(records)
    finally:
        leader.stop()
        follower._thread.join(timeout=60)
        syncs["leader"].close()
        syncs["follower"].close()
        assert not follower._thread.is_alive()
        assert follower.error is None

    got = _read_output(str(tmp_path / "out"))
    by_id = {rs[0]["id"]: rs[0] for rs in got.values()}
    for r in records:
        assert by_id[r["id"]]["tokens"] == want[r["id"]], r["id"]
    assert isinstance(NullSink(), object)  # transport import sanity


# --- per-record adapter selection (multi-tenant composition) ------------


def test_manifest_model_field_selects_adapter(tmp_path):
    """A record's `model` field must decode under that tenant's LoRA
    slot, token-exact vs the interactive engine given the same adapter
    (serve/adapters.py composition)."""
    from multihost_serve_worker import build_adapter_store

    cfg = _cfg()
    records = []
    for i, r in enumerate(_records(6, seed=11)):
        r["model"] = f"t{i % 2}"
        records.append(r)
    man = tmp_path / "m.jsonl"
    write_manifest(str(man), records)

    ref_engine = _engine(cfg, adapters=build_adapter_store(cfg, 2))
    try:
        want = {
            r["id"]: ref_engine.generate(
                list(r["tokens"]), max_tokens=r["max_tokens"],
                temperature=0.0, adapter=r["model"],
            )
            for r in records
        }
    finally:
        ref_engine.stop()
    # Distinct tenants must actually diverge, or this test proves nothing.
    assert want["r0"] != want["r1"] or want["r2"] != want["r3"]

    eng = _engine(cfg, adapters=build_adapter_store(cfg, 2))
    try:
        summary = BatchGenDriver(
            [eng], str(man), str(tmp_path / "out")
        ).run()
    finally:
        eng.stop()
    assert summary["errors"] == 0
    by_id = {
        rs[0]["id"]: rs[0]
        for rs in _read_output(str(tmp_path / "out")).values()
    }
    for r in records:
        assert by_id[r["id"]]["tokens"] == want[r["id"]], r["id"]
        assert by_id[r["id"]]["model"] == r["model"]


# --- failure accounting: bad records poison nothing ---------------------


def test_bad_records_written_once_as_errors(tmp_path):
    """A record with an unknown adapter and a record with no prompt must
    each produce ONE durable non-ok output line — the rest of the
    manifest generates normally and a resume run regenerates nothing."""
    records = _records(5, seed=13)
    records[1] = {"id": "noprompt"}  # neither prompt nor tokens
    records[3] = dict(records[3], model="no-such-tenant")
    man = tmp_path / "m.jsonl"
    write_manifest(str(man), records)

    eng = _engine()
    try:
        summary = BatchGenDriver(
            [eng], str(man), str(tmp_path / "out")
        ).run()
    finally:
        eng.stop()
    assert summary["written"] == 5
    assert summary["ok"] == 3
    assert summary["errors"] == 2
    by_id = {
        rs[0]["id"]: rs[0]
        for rs in _read_output(str(tmp_path / "out")).values()
    }
    assert by_id["noprompt"]["finish_reason"].startswith("invalid")
    assert by_id[records[3]["id"]]["finish_reason"] == "error"

    # Resume: everything (including the failures) is durable — the
    # rerun has nothing to do.
    eng = _engine()
    try:
        again = BatchGenDriver(
            [eng], str(man), str(tmp_path / "out")
        ).run()
    finally:
        eng.stop()
    assert again["resumed"] == 5 and again["written"] == 0


# --- progress surface: /loadz + metrics ---------------------------------


def test_progress_loadz_and_metrics(tmp_path):
    """load_snapshot() carries batchgen progress while a source is
    attached, the optional ProgressServer serves it on /loadz, and the
    shared registry carries the records/occupancy/progress series."""
    import threading
    import urllib.request

    from substratus_tpu.observability.metrics import METRICS

    records = _records(12, seed=17, lo_mt=8, hi_mt=12)
    man = tmp_path / "m.jsonl"
    write_manifest(str(man), records)

    eng = _engine()
    srv = ProgressServer(eng, host="127.0.0.1", port=0)
    # Each record's write-out takes a while (the hook runs on the sink
    # thread after the write), so the poller meets the run half done.
    driver = BatchGenDriver(
        [eng], str(man), str(tmp_path / "out"),
        record_hook=lambda out, prompt: time.sleep(0.05),
    )
    seen = {}
    done = threading.Event()

    def poll():
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not done.is_set():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/loadz", timeout=5
            ) as r:
                snap = json.loads(r.read())
            bg = snap.get("batchgen")
            if bg and 0 < bg["written"] < bg["manifest_records"]:
                seen.update(bg)
                return

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        summary = driver.run()
        done.set()
        poller.join(timeout=120)
    finally:
        srv.close()
        eng.stop()
    assert summary["written"] == len(records)
    assert seen, "never observed mid-run /loadz progress"
    assert seen["manifest_records"] == len(records)

    assert METRICS.get(
        "substratus_batchgen_records_total", {"outcome": "ok"}
    ) >= len(records)
    text = METRICS.render()
    assert "substratus_batchgen_slot_occupancy" in text
    assert "substratus_batchgen_manifest_progress_ratio" in text
    # Source detached after run(): the snapshot drops the progress key.
    assert "batchgen" not in eng.load_snapshot()


# --- manifest/shard units ----------------------------------------------


def test_manifest_units(tmp_path):
    man = tmp_path / "m.jsonl"
    man.write_text(
        '{"id": "a", "tokens": [1, 2]}\n'
        "\n"
        '{"id": "b", "prompt": "hi"}\n'
    )
    recs = list(iter_manifest(str(man)))
    # Index = line number, so blank lines never shift identities.
    assert [i for i, _ in recs] == [0, 2]
    assert count_records(str(man)) == 2

    out = tmp_path / "out"
    out.mkdir()
    (out / "shard-00000.jsonl").write_text(
        '{"index": 0, "tokens": [5]}\n'
        '{"index": 2, "tok'  # torn tail from a kill: ignored
    )
    (out / "not-a-shard.txt").write_text('{"index": 7}\n')
    assert completed_indices(str(out)) == {0}
    assert next_shard_index(str(out)) == 1

    man.write_text('{"id": "a", "tokens": [1,\n')
    with pytest.raises(ValueError, match="malformed manifest line"):
        list(iter_manifest(str(man)))


def test_source_rejected_on_decode_role():
    """A decode-role engine takes migrations, not pull sources."""
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    eng = Engine.__new__(Engine)  # no construction: role check is first
    eng.ec = EngineConfig(role="decode")
    eng.sync = None
    with pytest.raises(RuntimeError, match="decode-role"):
        Engine.set_source(eng, object())
    assert params is not None
