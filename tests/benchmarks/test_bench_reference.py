"""The plain float32 reference against the program at a small size on the
CPU, the controls that must fail (the program's own lower-precision paths
switched on), and a whole run with the timed path broken underneath."""
import numpy as np
import pytest

from benchmarks.harness import check, manifest as M
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W

MAN = M.load()


def small(name, **over):
    import json

    cfg = json.load(open(M.BENCH / "configs" / f"{name}.json"))
    cfg = dict(cfg, **{k: v for k, v in cfg["rehearse"].items() if k != "why"})
    cfg.update(over)
    return cfg


CONFIGS = {
    "mistral": lambda: small("mistral-7b-instruct-v0.2"),
    "mixtral": lambda: small("mixtral-8x7b-instruct-v0.1"),
}


def weights(cfg, seed):
    return W.make_weights(M.family_of(cfg).leaf_table(cfg), seed)


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg = CONFIGS[request.param]()
    return cfg, weights(cfg, 2**31 + 3)


def test_weights_are_seeded_int8_with_per_channel_scales(model):
    cfg, w = model
    again = weights(cfg, 2**31 + 3)
    other = weights(cfg, 2**31 + 4)
    wq = w["layers"]["wq"]
    assert wq["q"].dtype == np.int8 and wq["scale"].dtype == np.float32
    assert np.array_equal(wq["q"], again["layers"]["wq"]["q"])
    assert not np.array_equal(wq["q"], other["layers"]["wq"]["q"])
    assert int(np.min(wq["q"])) >= -127
    s = np.asarray(wq["scale"])
    assert s.shape == (wq["q"].shape[0], 1) + wq["q"].shape[2:]
    assert s.max() / s.min() > 1.2  # scales differ from channel to channel
    assert str(w["tok_embed"].dtype) == "bfloat16"
    shapes = W.tree_shapes(M.family_of(cfg).leaf_table(cfg))
    assert shapes["layers"]["wq"]["q"].shape == wq["q"].shape


def test_weight_layout_is_the_programs(model):
    """The family's own table of contracting dims equals the program's."""
    from benchmarks.harness import system
    from substratus_tpu.models import registry

    cfg, _ = model
    family = M.family_of(cfg)
    module = registry.module_for(family.program(cfg)[0])
    mcfg = system.model_config(family, cfg)
    theirs = module.quant_contracting(mcfg)
    for path, leaf in family.leaf_table(cfg).items():
        assert tuple(W.at(theirs, path)) == tuple(leaf.contracting), path
        assert (leaf.kind == "int8") is bool(leaf.contracting), path
        assert leaf.stacked is path.startswith("layers/"), path
    import jax

    ref_shapes = jax.eval_shape(lambda k: module.init_params(mcfg, k),
                                jax.random.key(0))
    for path, leaf in family.leaf_table(cfg).items():
        assert tuple(W.at(ref_shapes, path).shape) == tuple(leaf.shape), path


def test_reference_matches_the_programs_forward(model):
    """Full-sequence logits: the program in bfloat16 against the float32
    reference, on the same seeded int8 weights."""
    import jax.numpy as jnp

    from benchmarks.harness import system
    from substratus_tpu.models import llama

    cfg, w = model
    family = M.family_of(cfg)
    ref = M.reference_of(cfg)
    tokens = T.prompt_tokens(5, 0, 48, cfg["vocab_size"])
    rows = list(range(48))
    want = np.asarray(ref.logits_at(w, cfg, tokens, rows, pad_to=16))
    got, _ = llama.forward(system._wrap(w), jnp.asarray([tokens], jnp.int32),
                           system.model_config(family, cfg))
    got = np.asarray(got[0])
    # bfloat16 activations against float32: logits of about +-3 agree to a
    # few hundredths; a wrong rotary convention, scale axis, norm or expert
    # mix changes them by whole units.
    assert np.max(np.abs(got - want)) < 0.15
    assert np.mean(np.argmax(got, -1) == np.argmax(want, -1)) > 0.9
    assert np.std(want) > 0.3  # the logits are not degenerate


def test_served_gaps_are_zero_for_the_references_own_choice(model):
    cfg, w = model
    ref = M.reference_of(cfg)
    prompt = T.prompt_tokens(3, 0, 20, cfg["vocab_size"])
    served = []
    for _ in range(4):  # greedy by the reference itself
        seq = prompt + served
        lg = ref.logits_at(w, cfg, seq, [len(seq) - 1], pad_to=16)
        served.append(int(np.argmax(np.asarray(lg)[0])))
    g = ref.served_gaps(w, cfg, prompt, served)
    assert g.shape == (4,) and float(g.max()) == 0.0
    wrong = list(served)
    wrong[2] = (wrong[2] + 1) % cfg["vocab_size"]
    assert ref.served_gaps(w, cfg, prompt, wrong)[2] > 0.0


@pytest.mark.parametrize("control,key,want", [
    (None, None, None), ("int4", "weights", "int4"),
    ("w8a8", "activations", "int8"), ("int8kv", "kv_cache", "int8"),
])
def test_the_engines_types_are_read_from_what_it_holds(control, key, want):
    from benchmarks.harness import system

    cfg = CONFIGS["mistral"]()
    family = M.family_of(cfg)
    table = family.leaf_table(cfg)
    sizes = M.traffic_of("chat")["rehearse"]["engine"]
    w = W.make_weights(table, 5)
    if control == "int4":
        w = system.lower_weights(w, table)
    engine = system.build_engine(family, cfg, sizes, w, None, control)
    found = system.precision_found(engine, table)
    stated = {k: cfg["precision"][k] for k in found}
    assert {k for k in found if found[k] != stated[k]} == ({key} if key else set())
    if key:
        assert found[key] == want


def test_the_programs_int4_weights_are_made_from_the_same_int8_values(model):
    """lower_weights: the program's packed int4 of the harness's int8 tree,
    within one int4 step of it, and the int8 leaves are given up."""
    from benchmarks.harness import system

    cfg, _ = model
    w = weights(cfg, 11)
    q, scale = np.asarray(w["layers"]["wq"]["q"]), np.asarray(w["layers"]["wq"]["scale"])
    low = system.lower_weights(w, M.family_of(cfg).leaf_table(cfg))
    assert w["layers"]["wq"]["q"].is_deleted()
    got = np.asarray(low["layers"]["wq"].dequant(np.float32))
    want = q.astype(np.float32) * scale
    step = np.abs(want).max(axis=1, keepdims=True) / 7.0
    assert got.shape == want.shape and np.all(np.abs(got - want) <= 0.51 * step)
    assert str(low["tok_embed"].dtype) == "bfloat16"  # dense leaves pass through


class _Rec:
    def __init__(self, i, p, o, done, full=True):
        self.planned = T.Planned(i, -1, 0.0, p, o)
        self.sink = T.Sink()
        self.sink.ts = [done - 1.0] * (o if full else o - 1)
        self.sink.ids = [1] * len(self.sink.ts)
        self.sink.done_ts = done
        self.prompt = [0] * p

    first = property(lambda s: s.sink.ts[0] if s.sink.ts else None)
    done = property(lambda s: s.sink.done_ts)


def test_sample_holds_the_longest_finished_request_and_is_seeded():
    recs = [_Rec(i, 10 + i, 5, 10.0 + i) for i in range(8)]
    recs.append(_Rec(8, 500, 5, 100.0))       # finished after the window
    recs.append(_Rec(9, 400, 5, 12.0, full=False))  # a token short
    a = check.sample_finished(recs, 9.0, 20.0, seed=5, k=3)
    assert len(a) == 3 and a[0].planned.index == 7  # the longest inside
    assert [r.planned.index for r in a] == [
        r.planned.index for r in check.sample_finished(recs, 9.0, 20.0, 5, 3)]
    assert len({r.planned.index for r in a}) == 3
    assert check.sample_finished(recs, 200.0, 300.0, 5, 3) == []


def test_sample_holds_the_request_that_boarded_with_most_others_decoding():
    # request i decodes over [i, i + 4): number 3 boards beside 0, 1 and 2;
    # later ones beside three too, and the earliest of those is taken
    recs = []
    for i in range(6):
        r = _Rec(i, 10, 5, float(i + 4))
        r.sink.ts = [float(i)] + [float(i) + 0.5] * 4
        recs.append(r)
    a = check.sample_finished(recs, 0.0, 50.0, seed=1, k=2)
    assert [r.planned.index for r in a] == [0, 3]  # the longest tie, then it


LIMITS = {"gap_max": 1.0, "gap_mean": 1.0}


class _Ref:
    @staticmethod
    def served_gaps(weights, cfg, prompt, served):
        return np.zeros(len(served))


@pytest.mark.parametrize("found,ok", [
    ({"weights": "int8", "activations": "bfloat16", "kv_cache": "bfloat16"}, True),
    ({"weights": "int8", "activations": "bfloat16", "kv_cache": "int8"}, False),
    ({"weights": "int8", "activations": "int8", "kv_cache": "bfloat16"}, False),
    ({"weights": "int4", "activations": "bfloat16", "kv_cache": "bfloat16"}, False),
])
def test_a_type_other_than_the_configuration_states_is_not_correct(found, ok):
    stated = M.config_of(MAN, "mistral-7b-instruct-v0.2")["precision"]
    v = check.compare(_Ref, None, {}, [_Rec(0, 4, 3, 1.0)], LIMITS,
                      stated=stated, found=found)
    assert v["correct"] is ok
    n = v["numbers"]["precision_other_than_stated"]
    assert n["limit"] == 0.0 and (n["value"] == 0.0) is ok
    assert v["precision"]["found"] == found


def test_compare_without_a_finished_request_is_not_correct():
    v = check.compare(None, None, {}, [], LIMITS)
    assert v["correct"] is False


def _drive(monkeypatch, break_engine, control=None, seed=77):
    """benchmarks.run's whole run at the rehearsal size, in this process,
    without the look for a chip."""
    from benchmarks import run as R
    from benchmarks.harness import system

    man, cell, cfg, mix = R.resolve("mistral-7b.chat", rehearse=True)
    if break_engine:
        real = system.build_engine

        def broken(*a, **kw):
            engine = real(*a, **kw)
            decode = engine._decode_fn

            def altered(*args, **kwargs):
                tokens, cache, key = decode(*args, **kwargs)
                # a token altered where it is produced
                return (tokens + 1) % int(cfg["vocab_size"]), cache, key

            engine._decode_fn = altered
            return engine

        monkeypatch.setattr(system, "build_engine", broken)
    said = []
    monkeypatch.setattr(R, "_say", lambda *a: said.append(" ".join(map(str, a))))
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    result = R.run_once(man, cell, cfg, mix, 1, seed, 1.5, False, True, control,
                        device)
    return result, said


@pytest.mark.parametrize("broken", [False, True])
def test_a_run_with_the_timed_path_broken_is_not_correct(monkeypatch, broken):
    result, _ = _drive(monkeypatch, broken)
    assert result["attempted"] > 0
    assert result["correct"] is (not broken)


def _numbers(said, head):
    import json

    line = next(l for l in said if l.startswith(head))
    return json.loads(line[len(head):])["numbers"]


@pytest.mark.parametrize("seed", [77, 78, 2**31 + 79])
def test_the_programs_int4_weights_fail_the_gap_limits(monkeypatch, seed):
    """The control: the same run with the program's own int4 path switched
    on (ops/quant4.py), the step below the configuration's int8 weights,
    fails a gap's limit by itself, types aside; a sound run passes both."""
    _, said = _drive(monkeypatch, False, "int4", seed)
    n = _numbers(said, "control: ")
    assert n["precision_other_than_stated"]["value"] == 1.0
    assert (n["gap_max"]["value"] > n["gap_max"]["limit"]
            or n["gap_mean"]["value"] > n["gap_mean"]["limit"]), n
    result, said = _drive(monkeypatch, False, None, seed)
    n = _numbers(said, "correct: ")
    assert result["correct"] is True
    assert 3 * n["gap_max"]["value"] <= n["gap_max"]["limit"], n
    assert 3 * n["gap_mean"]["value"] <= n["gap_mean"]["limit"], n


@pytest.mark.parametrize("control", ["w8a8", "int8kv"])
def test_int8_below_bfloat16_is_caught_by_its_type(monkeypatch, control):
    """int8 activations or an int8 KV cache, the step below the stated
    bfloat16: the run is not correct because the engine holds another type
    than the configuration states, whatever the gaps read."""
    result, said = _drive(monkeypatch, False, control)
    assert result["correct"] is False
    assert _numbers(said, "control: ")["precision_other_than_stated"]["value"] == 1.0


def test_an_unknown_control_is_refused(monkeypatch):
    with pytest.raises(ValueError, match="control"):
        _drive(monkeypatch, False, "fp4")
