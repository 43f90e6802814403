"""The Brumby family's files (benchmarks/families/brumby.py, its reference,
the configuration brumby-14b-base-l10 and the mix longctx) as
test_bench_lfm2.py walks LFM2's: the published widths against the catalog,
the weight tree against the program's, the counts against hand-computed
bytes and FLOPs at the published and at the rehearsal size, the reference
against the program's forward, the controls that must fail, and the new
readers on runs that have nothing for them to read."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks.harness import counts, manifest as M
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W

MAN = M.load()
NAME, CELL = "brumby-14b-base-l10", "brumby-14b.longctx"
CFG = json.load(open(M.BENCH / "configs" / f"{NAME}.json"))
F = M.family_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

D, H, KH, HD, MD, V, L = 5120, 40, 8, 128, 17408, 151936, 10
PHI = HD * (HD + 1) // 2  # 8,256
PROJ = 2 * D * H * HD + 2 * D * KH * HD  # W_q, W_o, W_k, W_v: 62,914,560
LAYER = PROJ + 3 * D * MD  # 330,301,440
NEW_READERS = ["decode_retention_ms", "decode_retention_hbm_share",
               "decode_state_rows_live_share", "chunk_retention_ms",
               "chunk_retention_mxu_share"]


def small(**over):
    cfg = dict(CFG, **{k: v for k, v in CFG["rehearse"].items() if k != "why"})
    cfg.update(over)
    return cfg


def test_dims_from_published_keys():
    s = F.dims(CFG)
    assert (s["D"], s["H"], s["KH"], s["hd"], s["F"], s["M"], s["V"],
            s["L"]) == (D, H, KH, HD, PHI, MD, V, L)
    assert F.dims(small())["F"] == 136  # heads of 16


def test_config_file_keeps_published_widths_and_says_what_it_cut():
    entry = next(c for c in MAN["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"],
            CFG["intermediate_size"], CFG["vocab_size"],
            CFG["tie_word_embeddings"]) == (D, H, KH, HD, MD, V, False)
    assert CFG["published"]["num_hidden_layers"] == 40
    # the floor of a cut in depth: the pattern's period is one layer, and
    # four layers stand; here ten, every one whole, the whole vocabulary
    assert CFG["num_hidden_layers"] == L >= 4
    assert CFG["precision"] == {**CFG["precision"], "weights": "int8",
                                "activations": "bfloat16",
                                "kv_cache": "bfloat16", "state": "float32"}
    assert CFG["layout"]["chips"] == M.cell(MAN, CELL)["chips"] == 1
    assert CFG["layout"]["vocab_rows_held"] == [0, V]
    assert CFG["layout"]["layers_held"] == [0, L]
    assert "four pipeline stages of 10 layers" in CFG["layout"]["deployment"]
    assert "5.45 GB for 16 slots" in CFG["layout"]["bytes"]
    for key in ("power", "gate", "gate_shift", "gate_shift_why", "scale",
                "normaliser", "qk_norm", "modeling", "weights"):
        assert CFG["assumed"][key]
    for name in CFG["reduced"]:
        assert not name.endswith(("_dim", "_rank", "_size"))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_held_or_listed_as_reduced():
    row = next(json.loads(l) for l in open(CATALOG)
               if json.loads(l)["name"] == "Brumby-14B-Base")
    assert row["source_url"] == CFG["source"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value, key
            assert CFG["published"][key] == value, key
        else:
            assert CFG[key] == value, key


def test_weight_layout_is_the_programs():
    """The table's shapes and contracting dims are those of the program's
    own tree, at the published widths (shapes only: nothing is made)."""
    import jax

    from benchmarks.harness import system
    from substratus_tpu.models import registry

    module = registry.module_for(F.program(CFG)[0])
    mcfg = system.model_config(F, CFG)
    assert (mcfg.n_layers, mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim) == (
        L, H, KH, HD)
    assert mcfg.gate_shift == CFG["assumed"]["gate_shift"] == 9.0
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
    assert table["layers/b_gamma"].kind == "bias"  # float32, as the program's
    assert W.at(shapes, "layers/b_gamma").dtype == np.float32
    assert table["layers/w_gamma"].shape == (L, D, KH)
    assert table["lm_head"].shape == (D, V)  # a head of its own
    # the state: what the cache dict holds beside a pool of no layers
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        mcfg, 9217, 16, slots=16))
    assert cache["k"].shape == (0, 9217, 16, KH, HD) == cache["v"].shape
    assert cache["ret_s"].shape == (L, 16, KH, PHI, HD)
    assert cache["ret_z"].shape == (L, 16, KH, PHI)
    state = sum(math.prod(cache[n].shape) * 4 for n in ("ret_s", "ret_z"))
    assert 5.45e9 < state < 5.46e9  # 16 slots of 340.8 MB


def test_weight_bytes_are_the_issues_arithmetic():
    wb = counts.weight_bytes(F.leaf_table(CFG))
    assert wb["layers/wq"] == L * (D * H * HD + 4 * H * HD)
    assert wb["layers/w_gate"] == L * (D * MD + 4 * MD)
    assert wb["tok_embed"] == 2 * V * D  # 1.56 GB, bfloat16
    assert wb["lm_head"] == D * V + 4 * V  # 0.78 GB, int8
    assert LAYER == 330_301_440
    n = sum(math.prod(leaf.shape) for leaf in F.leaf_table(CFG).values())
    assert n == (L * (LAYER + D * KH + KH + 2 * D + 2 * HD)
                 + 2 * V * D + D)
    total = sum(wb.values())
    assert 5.63e9 < total < 5.66e9


def _hand_counts(cfg):
    s = F.dims(cfg)
    state = s["KH"] * s["F"] * (s["hd"] + 1) * 4  # S and z of a slot, float32
    acts = (2 * s["H"] + 2 * s["KH"]) * s["hd"] * 2 + 4 * s["KH"]
    read_out = 2 * s["H"] * s["F"] * (s["hd"] + 1)  # a token
    update = 2 * s["KH"] * s["F"] * (s["hd"] + 1)
    return s, state, acts, read_out, update


@pytest.mark.parametrize("size", ["published", "rehearsal"])
def test_decode_bytes_count_the_least_work(size):
    cfg = CFG if size == "published" else small()
    s, state, acts, _, _ = _hand_counts(cfg)
    table = counts.weight_bytes(F.leaf_table(cfg))
    not_streamed = sum(table[n] for n in (
        "tok_embed", "out_norm", "layers/input_norm", "layers/post_norm",
        "layers/q_norm", "layers/k_norm"))
    streamed = sum(table.values()) - not_streamed
    assert F.decode_matmul_weight_bytes(cfg, 1) == streamed
    assert F.decode_matmul_weight_bytes(cfg, 16) == streamed  # no expert
    # the live slots' S and z once read and once written, q, k, v and the
    # output in bfloat16, the gate's log in float32
    assert F.decode_retention_bytes(cfg, 12, 2) == 12 * s["L"] * (
        2 * state + acts)
    # no term follows a slot's context
    assert F.decode_step_bytes(cfg, [9000, 24], 2) == (
        streamed + 2 * s["L"] * (2 * state + acts))
    assert F.decode_step_bytes(cfg, [1, 1], 2) == F.decode_step_bytes(
        cfg, [9000, 24], 2)
    if size == "published":
        assert state == 8 * 8256 * 129 * 4 == 34_080_768  # 34.1 MB
        assert 4.07e9 < streamed < 4.10e9  # 10 layers + the head
        # 16 slots x 10 layers x 34.1 MB read and written: 10.9 GB
        assert 10.9e9 < F.decode_retention_bytes(CFG, 16, 2) < 10.92e9
    else:
        assert state == 2 * 136 * 17 * 4 and s["L"] == 4


@pytest.mark.parametrize("size", ["published", "rehearsal"])
def test_chunk_flops_count_the_equations(size):
    cfg = CFG if size == "published" else small()
    s, _, _, read_out, update = _hand_counts(cfg)
    n = 512 if size == "published" else 32
    intra = 2 * s["H"] * (n * (n + 1) // 2) * 2 * s["hd"]
    want = s["L"] * (n * (read_out + update) + intra)
    assert F.chunk_retention_flops(cfg, n) == want
    per_token = s["L"] * (
        2 * s["D"] * (s["H"] + s["KH"]) * s["hd"] + s["D"] * s["KH"]
        + 3 * s["D"] * s["M"])
    assert F.matmul_params_per_token(cfg) == per_token
    # the same at every offset: the operator reads a state, not a context
    assert F.prefill_chunk_flops(cfg, n, 0) == F.prefill_chunk_flops(
        cfg, n, 4096) == 2 * n * per_token + want + 2 * s["D"] * s["V"]
    if size == "published":
        # the issue's reckoning a layer: 43 + 8.7 + 2.7 GFLOP, 0.55 TFLOP
        assert 43e9 < 512 * read_out * 128 / 129 < 43.5e9
        assert 8.6e9 < 512 * update * 128 / 129 < 8.7e9
        assert 2.6e9 < intra < 2.7e9
        assert 0.54e12 < want < 0.56e12
        assert LAYER + D * KH == per_token // L


def test_regions_are_the_programs():
    from substratus_tpu.ops import scopes

    assert set(F.SCOPES) == set(scopes.RET) == {"ret.state", "ret.intra"}
    assert set(F.MATMUL_SCOPES) == {"attn.qkv", "attn.out", "mlp", "lm_head"}
    assert set(F.MATMUL_SCOPES) <= set(scopes.EVERY)


@pytest.fixture(scope="module")
def model():
    cfg = small()
    return cfg, W.make_weights(F.leaf_table(cfg), 2**31 + 3)


def test_reference_matches_the_programs_forward(model):
    """The program in float32 against the reference on the same seeded int8
    weights, the whole sequence at once and then in chunks of 16 through
    the state: summation order alone."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import system
    from substratus_tpu.models import brumby

    cfg, w = model
    tokens = T.prompt_tokens(5, 0, 48, cfg["vocab_size"])
    want = np.asarray(M.reference_of(cfg).logits_at(
        w, cfg, tokens, list(range(48)), pad_to=16))
    mcfg = system.model_config(F, cfg).replace(dtype=jnp.float32)
    assert mcfg.n_layers == 4 and mcfg.gate_shift == 9.0
    params = system._wrap(w)
    with jax.default_matmul_precision("highest"):
        got, _ = brumby.forward(params, jnp.asarray([tokens], jnp.int32), mcfg)
        cache = brumby.init_paged_cache(mcfg, 8, 4, slots=2)
        rows = []
        for off in range(0, 48, 16):
            logits, cache = brumby.forward(
                params, jnp.asarray([tokens[off:off + 16]], jnp.int32), mcfg,
                positions=off + jnp.arange(16)[None], cache=cache,
                slots=jnp.asarray([1]))
            rows.append(np.asarray(logits[0]))
    assert np.max(np.abs(np.asarray(got[0]) - want)) < 1e-4
    assert np.max(np.abs(np.concatenate(rows) - want)) < 1e-4
    assert np.std(want) > 0.3  # the logits are not degenerate


def test_the_seeded_gate_sits_near_one(model):
    """`assumed.gate_shift`: with the harness's bias around zero the gate's
    pre-activation is 9 +- 1, so the state's memory (1 / the mean of 1 - g,
    some 4,900 tokens) spans the contexts of the configuration's cell."""
    import jax
    import jax.numpy as jnp

    cfg, w = model
    b = np.asarray(w["layers"]["b_gamma"])
    assert b.dtype == np.float32 and np.abs(b).max() < 0.1
    h = jax.random.normal(jax.random.key(0), (512, cfg["hidden_size"]))
    pre = (h @ w["layers"]["w_gamma"][0].astype(jnp.float32) + b[0]
           + cfg["assumed"]["gate_shift"])
    g = np.asarray(jax.nn.sigmoid(pre))
    assert 0.9998 < np.median(g) < 0.99995 and g.min() > 0.99
    assert 3000 < 1 / np.mean(1 - g) < 8000


def test_the_engine_holds_the_state_at_the_stated_type(model):
    """`precision.state`. The harness's comparison of types knows no such
    key (`harness/system.py::precision_found`), and on the chip the gaps
    read the same with the state in bfloat16 (the file's `correct.why`),
    so the cell's `correct` cannot hold the program to it. This does: a
    change that keeps the state in another type has to change the
    configuration's file and the family's counts with it."""
    from benchmarks.harness import system

    cfg, w = model
    sizes = M.traffic_of(M.cell(MAN, CELL)["traffic"])["rehearse"]["engine"]
    eng = system.build_engine(F, cfg, sizes, w, None)
    stated = np.dtype(cfg["precision"]["state"])
    assert stated == np.float32 and F.STATE_ITEMSIZE == stated.itemsize
    for leaf in ("ret_s", "ret_z"):
        assert eng.cache[leaf].dtype == stated, leaf
    assert "state" not in system.precision_found(eng, F.leaf_table(cfg))


def test_served_gaps_are_zero_for_the_references_own_choice(model):
    cfg, w = model
    ref = M.reference_of(cfg)
    prompt = T.prompt_tokens(3, 0, 20, cfg["vocab_size"])
    served = []
    for _ in range(4):
        seq = prompt + served
        lg = ref.logits_at(w, cfg, seq, [len(seq) - 1], pad_to=16)
        served.append(int(np.argmax(np.asarray(lg)[0])))
    g = ref.served_gaps(w, cfg, prompt, served)
    assert g.shape == (4,) and float(g.max()) == 0.0


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


@pytest.mark.parametrize("control", [None, "int4", "w8a8"])
def test_rehearsal_is_correct_and_the_controls_are_not(monkeypatch, control):
    result, verdict = _drive(monkeypatch, control)
    n = verdict["numbers"]
    if control is None:
        assert result["correct"] is True and result["failed"] == 0
        # every `decode_` reader follows the step's length, which a CPU
        # says nothing of: the new ones report nothing here, as the held
        per_layer = result["counts"]["per_layer"]
        assert not set(per_layer) & set(NEW_READERS)
        assert "compiles_in_window" in per_layer
        assert result["counts"]["preemptions"] == 0
        assert result["counts"]["prefill_buckets"] == [16, 32]
        assert verdict["precision"]["found"]["kv_cache"] == "bfloat16"
        return
    assert result["correct"] is False
    assert n["precision_other_than_stated"]["value"] == 1.0
    if control == "int4":  # fails each gap's limit by itself, types aside
        assert n["gap_max"]["value"] > n["gap_max"]["limit"], n
        assert n["gap_mean"]["value"] > n["gap_mean"]["limit"], n


def test_an_int8_cache_is_refused_for_this_family(monkeypatch):
    with pytest.raises(ValueError, match="int8"):
        _drive(monkeypatch, "int8kv")


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
    assert (m["source"] == "program_counter") == (
        name == "decode_state_rows_live_share")
    assert m["unit"] == ("ms" if name.endswith("_ms") else "%")


def test_the_counter_reader_reads_the_engines_deltas():
    run = {"config": CFG, "family": F, "rehearse": False,
           "counters": {"stats": {"state_rows_live_sum": 1200,
                                  "state_rows_sum": 1600}}}
    assert M.layer_reader("decode_state_rows_live_share")(run) == 75.0
    assert M.layer_reader("decode_state_rows_live_share")(
        dict(run, rehearse=True)) is None


def test_the_mix_is_paced_and_its_sizes_are_the_issues():
    mix = M.traffic_of("longctx")
    assert mix["loop"] == "open" and mix["engine"] == {
        "max_batch": 16, "max_seq_len": 9216, "max_prefill_len": 512,
        "kv_pool_tokens": 147456}
    assert mix["check_requests"] == 4 and mix["rate_why"] and mix["who"]
    assert 0.3 <= mix["rate_rps"] <= 1.2
    pairs = T.block_pairs(mix)
    prompts = sorted(p for p, _ in pairs)
    outs = sorted(o for _, o in pairs)
    assert 1024 <= prompts[0] and prompts[-1] <= 8192
    assert 256 <= outs[0] and outs[-1] <= 768
    assert all(p % 64 == 0 for p in prompts) and all(o % 8 == 0 for o in outs)
    assert 2500 < float(np.median(prompts)) < 3400  # log-uniform: 2,900
    assert 400 < float(np.median(outs)) < 490
    assert max(prompts) + max(outs) <= mix["engine"]["max_seq_len"]
    # every prompt carries state across a chunk boundary: 2 to 16 chunks
    assert all(p > mix["engine"]["max_prefill_len"] for p in prompts)
    # a pool of no layers costs no byte and never binds: every slot whole
    assert mix["engine"]["kv_pool_tokens"] == 16 * 9216
