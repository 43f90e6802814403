"""`decode_steps_sampled_share` (PR 39): the reader is a file found by its
name, listed for every cell that reports `itl_p50_ms`, reads the engine's
two counters and nothing where a program keeps none (the parent commit)."""
import pytest

from benchmarks.harness import manifest as M

MAN = M.load()
NAME = "decode_steps_sampled_share"
CELLS = [w["name"] for w in MAN["workloads"]]


def test_the_entry_is_the_last_and_its_reader_is_a_file():
    entry = MAN["per_layer"][-1]
    step = next(m for m in MAN["per_layer"] if m["name"] == "decode_step_ms")
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": step["layer"],
        "moves": "itl_p50_ms",
    }
    assert (M.BENCH / "layer_metrics" / f"{NAME}.py").is_file()
    assert callable(M.layer_reader(NAME))
    assert M.validate(MAN) == []


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_it_beside_the_metric_it_moves(cell):
    listed = {m["name"] for m in M.metrics_for(MAN, cell, "per_layer")}
    judged = {m["name"] for m in M.metrics_for(MAN, cell, "end_to_end")}
    assert NAME in listed and "itl_p50_ms" in judged


@pytest.mark.parametrize("stats,want", [
    ({"decode_steps": 2400, "decode_steps_sampled": 0}, 0.0),
    ({"decode_steps": 40, "decode_steps_sampled": 10}, 25.0),
    ({"decode_steps": 7, "decode_steps_sampled": 7}, 100.0),
    # a window with no decode step, and a program without the counters
    ({"decode_steps": 0, "decode_steps_sampled": 0}, None),
    ({"preemptions": 0, "prefill_tokens": 512}, None),
    ({}, None),
], ids=["greedy", "quarter", "all", "no_steps", "parent", "empty"])
def test_the_reader_reads_the_two_counters_or_nothing(stats, want):
    run = {"counters": {"stats": stats}, "trace": None, "rehearse": False}
    assert M.layer_reader(NAME)(run) == want
    assert M.layer_reader(NAME)(dict(run, rehearse=True)) is None


def test_a_rehearsals_counters_read_zero_and_a_rehearsal_reports_nothing(
        monkeypatch):
    """The engine's counters reach the reader through a whole run: every
    request of every mix is greedy, so the counters of a rehearsal read 0.0;
    the rehearsal itself writes nothing under a `decode_` name."""
    from benchmarks import run as R

    seen = {}
    real = M.layer_reader

    def spy(name, *a, **kw):
        reader = real(name, *a, **kw)

        def read(run):
            if name == NAME:
                seen["run"] = run
            return reader(run)
        return read

    monkeypatch.setattr(M, "layer_reader", spy)
    monkeypatch.setattr(R, "_say", lambda *a: None)
    man, cell, cfg, mix = R.resolve("mistral-7b.chat", rehearse=True)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    result = R.run_once(man, cell, cfg, mix, 1, 2**31 + 39, 1.5, True, True,
                        None, device)
    assert result["correct"] is True and result["failed"] == 0
    assert NAME not in result["counts"]["per_layer"]
    run = seen["run"]
    stats = run["counters"]["stats"]
    assert stats["decode_steps"] > 0 == stats["decode_steps_sampled"]
    assert real(NAME)(run) is None
    assert real(NAME)(dict(run, rehearse=False)) == 0.0
