"""The Granite-4.0-H family's files (benchmarks/families/granitemoehybrid.py,
its reference, the configuration granite-4.0-h-micro and the mix rag) as
test_bench_brumby.py walks Brumby's: the published keys against the catalog
(nothing is reduced), the weight tree against the program's and its bytes
against the engine's, the counts against hand-computed bytes and FLOPs at
the published and at the rehearsal size, the reference against the
program's forward, the seeded decay, the state's type, the control that
must fail, and the new readers on runs that have nothing for them to
read."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks.harness import counts, manifest as M
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W

MAN = M.load()
NAME, CELL = "granite-4.0-h-micro", "granite-4.0-h-micro.rag"
CFG = json.load(open(M.BENCH / "configs" / f"{NAME}.json"))
F = M.family_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

D, H, KH, HD, MD, V, L = 2048, 32, 8, 64, 8192, 100352, 40
LM, LA, HM, P, N, K = 36, 4, 64, 64, 128, 4
E, WC = HM * P, HM * P + 2 * N  # 4,096 and 4,352
MIXER = D * (E + WC + HM) + E * D  # in_proj 17.43 M + out_proj 8.39 M
ATTN = D * (H + 2 * KH) * HD + H * HD * D  # 10.49 M
MLP = 3 * D * MD  # 50.33 M
NEW_READERS = ["decode_ssm_ms", "decode_ssm_hbm_share", "decode_ssm_mixer_ms",
               "chunk_ssm_ms", "chunk_ssm_mxu_share",
               "decode_state_rows_live_share.granite",
               "conv_chunks_resumed_share.granite"]
COUNTERS = tuple(NEW_READERS[-2:])


def small(**over):
    cfg = dict(CFG, **{k: v for k, v in CFG["rehearse"].items() if k != "why"})
    cfg.update(over)
    return cfg


def test_dims_from_published_keys():
    s = F.dims(CFG)
    assert (s["D"], s["H"], s["KH"], s["hd"], s["M"], s["V"], s["L"]) == (
        D, H, KH, HD, MD, V, L)
    assert (s["Lm"], s["La"], s["Hm"], s["P"], s["N"], s["T"], s["E"],
            s["W"], s["block"]) == (LM, LA, HM, P, N, K, E, WC, 256)
    assert [i for i, k in enumerate(s["ops"]) if k == "attention"] == [
        5, 15, 25, 35]
    r = F.dims(small())
    assert r["ops"] == ("mamba", "mamba", "attention", "mamba") * 2
    assert (r["E"], r["W"], r["N"]) == (128, 160, 16)
    with pytest.raises(ValueError, match="no experts"):
        F.dims(dict(CFG, num_local_experts=72))


def test_config_file_keeps_every_published_key_and_cuts_nothing():
    entry = next(c for c in MAN["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == CFG["reduced"] == []
    assert CFG["published"]["num_hidden_layers"] == CFG[
        "num_hidden_layers"] == L
    assert (CFG["position_embedding_type"], CFG["attention_multiplier"],
            CFG["embedding_multiplier"], CFG["residual_multiplier"],
            CFG["logits_scaling"], CFG["tie_word_embeddings"]) == (
                "nope", 1 / 64, 12, 0.22, 8, True)
    assert CFG["precision"] == {**CFG["precision"], "weights": "int8",
                                "activations": "bfloat16",
                                "kv_cache": "bfloat16", "state": "float32"}
    assert CFG["layout"]["chips"] == M.cell(MAN, CELL)["chips"] == 1
    assert CFG["layout"]["vocab_rows_held"] == [0, V]
    assert CFG["layout"]["layers_held"] == [0, L]
    assert "3.62 GB for 48 slots" in CFG["layout"]["bytes"]
    for key in ("dt_shift", "shifts_why", "in_proj", "dt",
                "gated_norm", "conv", "attention", "mlp", "modeling",
                "weights"):
        assert CFG["assumed"][key] is not None, key
    assert CFG["correct"]["why"] and CFG["rehearse"]["correct"]["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_held_unchanged():
    row = next(json.loads(l) for l in open(CATALOG)
               if json.loads(l)["name"] == NAME)
    assert row["source_url"] == CFG["source"]
    for key, value in row["config"].items():
        assert CFG[key] == value, key


def test_weight_layout_is_the_programs():
    """The table's shapes and contracting dims are those of the program's
    own tree, at the published widths (shapes only: nothing is made), and
    the cache dict holds three histories."""
    import jax

    from benchmarks.harness import system
    from substratus_tpu.models import registry

    module = registry.module_for(F.program(CFG)[0])
    mcfg = system.model_config(F, CFG)
    assert module.layer_plan(mcfg) == (0, 10, 4)
    assert mcfg.dt_shift == CFG["assumed"]["dt_shift"]
    theirs = module.quant_contracting(mcfg)
    shapes = jax.eval_shape(lambda k: module.init_params(mcfg, k),
                            jax.random.key(0))
    table = F.leaf_table(CFG)
    assert len(table) == len(jax.tree.leaves(shapes))
    for path, leaf in table.items():
        assert tuple(W.at(theirs, path)) == tuple(leaf.contracting), path
        assert (leaf.kind == "int8") is bool(leaf.contracting), path
        assert leaf.stacked is ("/" in path), path
        assert tuple(W.at(shapes, path).shape) == tuple(leaf.shape), path
    for name in ("a_log", "dt_bias", "conv_bias"):  # float32, as the program's
        assert table[f"ssm/{name}"].kind == "bias"
        assert W.at(shapes, f"ssm/{name}").dtype == np.float32
    assert table["ssm/w_in"].shape == (LM, D, 8512)
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        mcfg, 27649, 16, slots=48))
    assert cache["k"].shape == (LA, 27649, 16, KH // 2, 128) == cache["v"].shape
    assert cache["conv"].shape == (LM, 48, (K - 1) * WC)
    assert cache["ssm"].shape == (LM, 48, N, E)
    assert cache["ssm"].dtype == np.float32
    assert math.prod(cache["ssm"].shape[2:]) * 4 == 2 << 20  # 2 MiB
    state = math.prod(cache["ssm"].shape) * 4
    pool = 2 * math.prod(cache["k"].shape) * 2
    assert 3.62e9 < state < 3.63e9 and 3.62e9 < pool < 3.63e9


def test_weight_bytes_are_the_issues_arithmetic():
    wb = counts.weight_bytes(F.leaf_table(CFG))
    assert wb["ssm/w_in"] == LM * (D * 8512 + 4 * 8512)
    assert wb["tok_embed"] == 2 * V * D  # 0.41 GB, bfloat16
    assert (MIXER, ATTN, MLP) == (25_821_184, 10_485_760, 50_331_648)
    matmul = LM * MIXER + LA * ATTN + L * MLP
    assert 2.98e9 < matmul < 2.99e9  # 2,986 M in layers
    n = sum(math.prod(leaf.shape) for leaf in F.leaf_table(CFG).values())
    assert n == (matmul + V * D + D + 2 * L * D
                 + LM * (K * WC + WC + 3 * HM + E))
    assert 3.19e9 < n < 3.20e9  # 3.19 B parameters
    assert 3.40e9 < sum(wb.values()) < 3.42e9  # 3.40 GB as served


def _hand_counts(cfg):
    s = F.dims(cfg)
    state = s["N"] * s["E"] * 4  # S of a slot and layer, float32
    rows = (s["T"] - 1) * s["W"] * 2
    acts = (s["E"] + 2 * s["N"]) * 2 + 4 * s["Hm"] + 4 * s["E"]
    return s, state, rows, acts


@pytest.mark.parametrize("size", ["published", "rehearsal"])
def test_decode_bytes_count_the_least_work(size):
    cfg = CFG if size == "published" else small()
    s, state, rows, acts = _hand_counts(cfg)
    table = counts.weight_bytes(F.leaf_table(cfg))
    not_streamed = sum(table[n] for n in (
        "out_norm", "layers/input_norm", "layers/post_norm", "ssm/taps",
        "ssm/conv_bias", "ssm/a_log", "ssm/d_skip", "ssm/dt_bias",
        "ssm/norm"))
    streamed = sum(table.values()) - not_streamed
    assert F.decode_matmul_weight_bytes(cfg, 1) == streamed
    assert F.decode_matmul_weight_bytes(cfg, 48) == streamed  # no expert
    # the live slots' S once read and once written, x, B, C in bfloat16,
    # dt and the output in float32
    assert F.decode_ssm_bytes(cfg, 30, 2) == 30 * s["Lm"] * (
        2 * state + acts)
    # the pages' term follows the live context, the state's the slots
    page_row = 2 * s["KH"] * s["hd"] * 2
    assert F.decode_step_bytes(cfg, [9000, 24], 2) == (
        streamed + 2 * s["Lm"] * 2 * (state + rows)
        + s["La"] * page_row * (9001 + 25))
    if size == "published":
        assert state == 2 << 20 and rows == 26_112
        assert 3.39e9 < streamed < 3.41e9  # the whole tree but 5 MB
        # 48 slots x 36 layers x 2 MiB read and written: 7.25 GB
        assert 7.24e9 < 48 * s["Lm"] * 2 * state < 7.26e9
        assert 7.28e9 < F.decode_ssm_bytes(CFG, 48, 2) < 7.32e9
        # 30 slots at 3,500 tokens: the pages are a twentieth of the step
        step = F.decode_step_bytes(CFG, [3500] * 30, 2)
        pages = 30 * LA * page_row * 3501
        assert 0.85e9 < pages < 0.87e9 and 0.09 < pages / step < 0.11
    else:
        assert state == 16 * 128 * 4 and s["Lm"] == 6


@pytest.mark.parametrize("size", ["published", "rehearsal"])
def test_chunk_flops_count_the_equations(size):
    cfg = CFG if size == "published" else small()
    s = F.dims(cfg)
    n = 512 if size == "published" else 32
    full, rest = divmod(n, 256)
    pairs = full * 256 * 257 // 2 + rest * (rest + 1) // 2
    want = s["Lm"] * (4 * n * s["N"] * s["E"]
                      + 2 * pairs * (s["N"] + s["E"]))
    assert F.chunk_ssm_flops(cfg, n) == want
    per_token = (s["Lm"] * (s["D"] * (s["E"] + s["W"] + s["Hm"])
                            + s["E"] * s["D"])
                 + s["La"] * (s["D"] * (s["H"] + 2 * s["KH"]) * s["hd"]
                              + s["H"] * s["hd"] * s["D"])
                 + s["L"] * 3 * s["D"] * s["M"])
    assert F.matmul_params_per_token(cfg) == per_token
    seen = n * 1024 + n * (n + 1) // 2
    assert F.prefill_chunk_flops(cfg, n, 1024) == (
        2 * n * per_token + 4 * s["H"] * s["hd"] * s["La"] * seen + want
        + 2 * n * s["Lm"] * s["T"] * s["W"] + 2 * s["D"] * s["V"])
    if size == "published":
        assert per_token == LM * MIXER + LA * ATTN + L * MLP
        # the issue's reckoning: about 3.3 TFLOP of matmuls (3.06 in the
        # layers) and the scan a fiftieth of it
        assert 3.0e12 < 2 * 512 * per_token < 3.1e12
        assert 0.05e12 < want < 0.07e12


def test_regions_are_the_programs():
    from benchmarks.harness import trace_scopes
    from substratus_tpu.ops import scopes

    assert set(F.SCOPES) == set(scopes.SSM) | {scopes.CONV_STATE}
    assert set(F.MATMUL_SCOPES) == {"ssm.in", "ssm.out", "attn.qkv",
                                    "attn.out", "mlp", "lm_head"}
    assert set(F.MATMUL_SCOPES) <= set(scopes.EVERY)
    assert set(F.SCOPES) <= trace_scopes.vocabulary()


@pytest.fixture(scope="module")
def model():
    cfg = small()
    return cfg, W.make_weights(F.leaf_table(cfg), 2**31 + 3)


def test_reference_matches_the_programs_forward(model):
    """The program in float32 against the reference on the same seeded int8
    weights, the whole sequence at once and then in chunks of 16 through
    pages, rows and state: summation order alone."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import system
    from substratus_tpu.models import granitemoehybrid as G

    cfg, w = model
    tokens = T.prompt_tokens(5, 0, 48, cfg["vocab_size"])
    want = np.asarray(M.reference_of(cfg).logits_at(
        w, cfg, tokens, list(range(48)), pad_to=16))
    mcfg = system.model_config(F, cfg).replace(dtype=jnp.float32)
    assert mcfg.n_layers == 8 and mcfg.dt_shift == -5.3
    params = system._wrap(w)
    fwd = jax.jit(lambda t, **kw: G.forward(params, t, mcfg, **kw))
    with jax.default_matmul_precision("highest"):
        got, _ = fwd(jnp.asarray([tokens], jnp.int32))
        cache = G.init_paged_cache(mcfg, 16, 4, slots=2)
        table = jnp.arange(1, 13, dtype=jnp.int32)[None]
        rows = []
        for off in range(0, 48, 16):
            logits, cache = fwd(
                jnp.asarray([tokens[off:off + 16]], jnp.int32),
                positions=off + jnp.arange(16)[None], cache=cache,
                block_table=table, slots=jnp.asarray([1]))
            rows.append(np.asarray(logits[0]))
    # logits spread by 0.007 here (the family file says why): 1e-6 is a
    # seven-thousandth of it
    assert np.max(np.abs(np.asarray(got[0]) - want)) < 1e-6
    assert np.max(np.abs(np.concatenate(rows) - want)) < 1e-6
    assert 0.004 < np.std(want) < 0.02
    # the tied head does not hand the prompt's last token back: its own
    # logit stands about one spread above the rest, not six
    own = [(want[i, t] - want[i].mean()) / want[i].std()
           for i, t in enumerate(tokens)]
    assert 0.3 < np.mean(own) < 2.0
    assert np.mean(want.argmax(-1) == np.asarray(tokens)) < 0.3


def test_the_seeded_decay_sits_near_one(model):
    """`assumed.dt_shift`: with the harness's vectors around zero `dt`'s
    pre-activation is -5.3 +- 1 and A is exp(0 +- 0.01) = 1, so the decay
    exp(dt A) is 0.96-0.9993 and the state's memory, 1 / (dt A), tens to
    hundreds of tokens."""
    import jax
    import jax.numpy as jnp

    cfg, w = model
    s = F.dims(cfg)
    for name in ("a_log", "dt_bias"):
        v = np.asarray(w["ssm"][name])
        assert v.dtype == np.float32 and np.abs(v).max() < 0.1
    h = jax.random.normal(jax.random.key(0), (512, cfg["hidden_size"]))
    w_in = w["ssm"]["w_in"]
    d = (h @ (w_in["q"][0].astype(jnp.float32) * w_in["scale"][0])
         )[:, s["E"] + s["W"]:]
    dt = jax.nn.softplus(d + w["ssm"]["dt_bias"][0]
                         + cfg["assumed"]["dt_shift"])
    a = np.exp(np.asarray(w["ssm"]["a_log"][0]))
    decay = np.exp(-np.asarray(dt) * a)
    assert 0.993 < np.median(decay) < 0.997
    assert np.quantile(decay, 0.02) > 0.95 and decay.max() < 0.99995
    assert 100 < 1 / np.median(np.asarray(dt) * a) < 400


def test_the_engine_holds_the_state_at_the_stated_type(model):
    """`precision.state`. The harness's comparison of types knows no such
    key (`harness/system.py::precision_found`), so the cell's `correct`
    cannot hold the program to it by the types. This does: a change that
    keeps the state in another type has to change the configuration's file
    and the family's counts with it. And the family's bytes are the
    engine's tree to the byte."""
    import jax

    from benchmarks.harness import system

    cfg, w = model
    sizes = M.traffic_of(M.cell(MAN, CELL)["traffic"])["rehearse"]["engine"]
    eng = system.build_engine(F, cfg, sizes, w, None)
    stated = np.dtype(cfg["precision"]["state"])
    assert stated == np.float32 and F.STATE_ITEMSIZE == stated.itemsize
    assert eng.cache["ssm"].dtype == stated
    assert str(eng.cache["conv"].dtype) == str(eng.cache["k"].dtype) == (
        "bfloat16")
    assert "state" not in system.precision_found(eng, F.leaf_table(cfg))
    held = sum(a.nbytes for a in jax.tree.leaves(eng.params))
    assert held == sum(counts.weight_bytes(F.leaf_table(cfg)).values())


def test_served_gaps_are_zero_for_the_references_own_choice(model):
    cfg, w = model
    ref = M.reference_of(cfg)
    prompt = T.prompt_tokens(3, 0, 20, cfg["vocab_size"])
    served = []
    for _ in range(3):
        seq = prompt + served
        lg = ref.logits_at(w, cfg, seq, [len(seq) - 1], pad_to=16)
        served.append(int(np.argmax(np.asarray(lg)[0])))
    g = ref.served_gaps(w, cfg, prompt, served)
    assert g.shape == (3,) and float(g.max()) == 0.0


def _drive(monkeypatch, control=None, seed=77):
    from benchmarks import run as R

    man, cell, cfg, mix = R.resolve(CELL, rehearse=True)
    said = []
    monkeypatch.setattr(R, "_say", lambda *a: said.append(" ".join(map(str, a))))
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    result = R.run_once(man, cell, cfg, mix, 1, seed, 1.5, True, True, control,
                        device)
    head = "control: " if control else "correct: "
    line = next(l for l in said if l.startswith(head))
    return result, json.loads(line[len(head):])


@pytest.mark.parametrize("control", [None, "int4"])
def test_rehearsal_is_correct_and_the_control_is_not(monkeypatch, control):
    result, verdict = _drive(monkeypatch, control)
    n = verdict["numbers"]
    if control is None:
        assert result["correct"] is True and result["failed"] == 0
        # every `decode_` reader follows the step's length, which a CPU
        # says nothing of: the new ones report nothing here, as the held;
        # the chunks' counter does
        per_layer = result["counts"]["per_layer"]
        assert set(per_layer) & set(NEW_READERS) == {
            "conv_chunks_resumed_share.granite"}
        assert 0 < per_layer[
            "conv_chunks_resumed_share.granite"]["value"] < 100
        assert "compiles_in_window" in per_layer
        assert result["counts"]["preemptions"] == 0
        assert result["counts"]["prefill_buckets"] == [16, 32]
        assert verdict["precision"]["found"]["kv_cache"] == "bfloat16"
        assert verdict["served_is_best_share"] < 1.0  # not the degenerate
        return
    assert result["correct"] is False
    assert n["precision_other_than_stated"]["value"] == 1.0
    # int4 fails each gap's limit by itself, types aside
    assert n["gap_max"]["value"] > n["gap_max"]["limit"], n
    assert n["gap_mean"]["value"] > n["gap_mean"]["limit"], n


def test_an_int8_cache_is_refused_for_this_family():
    from benchmarks.harness import system

    cfg = small()
    sizes = M.traffic_of("rag")["rehearse"]["engine"]
    with pytest.raises(ValueError, match="int8"):
        system.build_engine(F, cfg, sizes, None, None, "int8kv")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(name):
    """A program without the spans or counters (the parent commit), a
    family without the count, a run without a trace: nothing, no raise."""
    run = {"cell": {"name": "nowhere"}, "config": CFG,
           "family": M.family_of(json.load(open(
               M.BENCH / "configs" / "mistral-7b-instruct-v0.2.json"))),
           "mix": {"engine": {"max_prefill_len": 512}}, "chips": 1,
           "device": {"kind": "TPU v5 lite"},
           "records": [], "traced": (0.0, 1.0), "w0": 0.0, "w1": 1.0,
           "counters": {"stats": {"preemptions": 0}}, "trace": None,
           "rehearse": False}
    assert M.layer_reader(name)(run) is None
    assert M.layer_reader(name)(dict(run, family=F)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_are_listed_for_the_new_cell_alone(name):
    m = next(x for x in MAN["per_layer"] if x["name"] == name)
    assert m["workloads"] == [CELL] and m["moves"] == "itl_p50_ms"
    assert (m["source"] == "program_counter") == (name in COUNTERS)
    assert m["unit"] == ("ms" if name.endswith("_ms") else "%")
    assert MAN["per_layer"].index(m) >= len(MAN["per_layer"]) - len(
        NEW_READERS)  # appended


def test_the_counter_readers_read_the_engines_deltas():
    run = {"config": CFG, "family": F, "rehearse": False,
           "counters": {"stats": {"state_rows_live_sum": 1200,
                                  "state_rows_sum": 1600,
                                  "conv_chunks_sum": 40,
                                  "conv_chunks_resumed_sum": 34}}}
    assert M.layer_reader("decode_state_rows_live_share.granite")(run) == 75.0
    assert M.layer_reader("conv_chunks_resumed_share.granite")(run) == 85.0
    assert M.layer_reader("decode_state_rows_live_share.granite")(
        dict(run, rehearse=True)) is None


def test_the_mix_is_paced_and_its_sizes_are_the_issues():
    mix = M.traffic_of("rag")
    assert mix["loop"] == "open" and mix["engine"] == {
        "max_batch": 48, "max_seq_len": 9216, "max_prefill_len": 512,
        "kv_pool_tokens": 442368}
    assert mix["check_requests"] == 4 and mix["deadline_s"] == 60
    assert mix["rate_why"] and mix["who"] and mix["engine_why"]
    assert 0.5 <= mix["rate_rps"] <= 4.0
    pairs = T.block_pairs(mix)
    assert len(pairs) == mix["block"] == 24
    prompts = sorted(p for p, _ in pairs)
    outs = sorted(o for _, o in pairs)
    # ISSUE 46's ladder, second step: prompts 2,048-4,096 on the same grid
    assert 2048 <= prompts[0] and prompts[-1] <= 4096
    assert 256 <= outs[0] and outs[-1] <= 768
    assert all(p % 64 == 0 for p in prompts) and all(o % 8 == 0 for o in outs)
    assert 2700 < float(np.median(prompts)) < 3100  # log-uniform: 2,900
    assert 400 < float(np.median(outs)) < 490
    assert max(prompts) + max(outs) <= mix["engine"]["max_seq_len"]
    # every prompt carries rows and state across a chunk boundary
    assert all(p > 3 * mix["engine"]["max_prefill_len"] for p in prompts)
    # every slot whole: nothing is preempted
    assert mix["engine"]["kv_pool_tokens"] == 48 * 9216
    # the replies, the grid and the block are longctx's
    held = M.traffic_of("longctx")
    assert (mix["output_len"], mix["block"], mix["prompt_len"]["grid"]) == (
        held["output_len"], held["block"], held["prompt_len"]["grid"])
