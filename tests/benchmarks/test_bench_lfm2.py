"""The LFM2-MoE family's files (benchmarks/families/lfm2_moe.py, its
reference, the configuration lfm2-24b-a2b-l16 and the mix assist) as
test_bench_exaone.py walks K-EXAONE's: the published widths against the
catalog, the weight tree against the program's, the counts against
hand-computed bytes and FLOPs, the reference against the program's forward,
the controls that must fail, and the new readers on runs that have nothing
for them to read."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks.harness import counts, manifest as M
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W

MAN = M.load()
NAME, CELL = "lfm2-24b-a2b-l16", "lfm2-24b-a2b.assist"
CFG = json.load(open(M.BENCH / "configs" / f"{NAME}.json"))
F = M.family_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

D, H, KH, HD, MD, MM, V, L, E, K, TAPS = (2048, 32, 8, 64, 11776, 1536, 65536,
                                          16, 64, 4, 3)
ATTN = D * H * HD + 2 * D * KH * HD + H * HD * D  # 10,485,760
CONV = 3 * D * D + D * D                          # 16,777,216
EXPERT = 3 * D * MM                               # 9,437,184
NEW_READERS = ["decode_conv_ms", "decode_conv_hbm_share",
               "decode_moe_experts_hbm_share.whole",
               "moe_decode_experts_touched_share", "conv_chunks_resumed_share"]


def small(**over):
    cfg = dict(CFG, **{k: v for k, v in CFG["rehearse"].items() if k != "why"})
    cfg.update(over)
    return cfg


def test_dims_from_published_keys():
    s = F.dims(CFG)
    assert (s["D"], s["H"], s["KH"], s["hd"], s["M"], s["Mm"], s["V"], s["L"],
            s["E"], s["Eh"], s["first"], s["K"], s["T"]) == (
        D, H, KH, HD, MD, MM, V, L, E, E, 0, K, TAPS)
    assert s["ops"] == ("conv", "conv", "full_attention", "conv") * 4
    assert s["mlp"] == ("dense",) * 2 + ("sparse",) * 14
    assert (s["Lc"], s["La"], s["Ld"], s["Ls"]) == (12, 4, 2, 14)


def test_config_file_keeps_published_widths_and_says_what_it_cut():
    entry = next(c for c in MAN["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"], CFG["num_experts"],
            CFG["num_experts_per_tok"], CFG["conv_L_cache"],
            CFG["vocab_size"]) == (2048, 32, 8, 11776, 1536, 64, 4, 3, 65536)
    assert CFG["published"]["num_hidden_layers"] == 40
    assert len(CFG["layer_types"]) == 40  # copied whole; the first 16 held
    # the floors of a cut: a whole period and four layers after the dense
    # ones; here four whole periods, every expert, the whole vocabulary
    assert CFG["num_hidden_layers"] == 16 >= CFG["num_dense_layers"] + 4
    assert CFG["precision"] == {**CFG["precision"], "weights": "int8",
                                "activations": "bfloat16",
                                "kv_cache": "bfloat16"}
    assert CFG["layout"]["chips"] == M.cell(MAN, CELL)["chips"] == 1
    assert CFG["layout"]["experts_held"] == [0, 64]
    assert CFG["layout"]["vocab_rows_held"] == [0, 65536]
    assert "16 + 12 + 12" in CFG["layout"]["deployment"]
    for key in ("conv_thirds", "tie_embedding", "head_dim", "qk_norm",
                "router", "weights"):
        assert CFG["assumed"][key]
    for name in CFG["reduced"]:
        assert not name.endswith(("_dim", "_rank", "_size"))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_held_or_listed_as_reduced():
    row = next(json.loads(l) for l in open(CATALOG)
               if json.loads(l)["name"] == "LFM2-24B-A2B")
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
    assert mcfg.held_experts == (0, 64) and mcfg.n_experts == 64
    assert mcfg.n_shared_experts == 0 and mcfg.route_norm_eps == 1e-6
    assert (mcfg.count("conv"), mcfg.count("full_attention")) == (12, 4)
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
    assert table["moe/router_bias"].kind == "bias"
    assert table["conv/taps"].shape == (12, 3, D)
    assert "lm_head" not in table  # the head is the embedding


def test_weight_bytes_are_the_issues_arithmetic():
    wb = counts.weight_bytes(F.leaf_table(CFG))
    assert wb["moe/w_gate"] == 14 * 64 * D * MM + 4 * 14 * 64 * MM
    assert wb["tok_embed"] == 2 * V * D
    experts = sum(wb[n] for n in ("moe/w_gate", "moe/w_up", "moe/w_down"))
    assert 8.45e9 < experts < 8.48e9  # 14 x 604 MB + 18 MB of scales
    total = sum(wb.values())
    assert 9.1e9 < total < 9.2e9
    n = sum(math.prod(leaf.shape) for leaf in F.leaf_table(CFG).values())
    assert n == (12 * (CONV + TAPS * D) + 4 * (ATTN + 2 * HD) + 2 * 3 * D * MD
                 + 14 * (64 * EXPERT + D * E + E) + L * 2 * D + V * D + D)


def test_decode_bytes_count_the_least_work():
    table = counts.weight_bytes(F.leaf_table(CFG))
    experts = sum(table[n] for n in ("moe/w_gate", "moe/w_up", "moe/w_down"))
    norms = sum(table[n] for n in ("out_norm", "layers/operator_norm",
                                   "layers/ffn_norm", "attn/q_norm",
                                   "attn/k_norm"))
    taps = table["conv/taps"]
    streamed = sum(table.values()) - norms - taps  # the embedding is the head
    # one slot can route to 4 of the 64 experts, sixteen or more to all
    assert F.decode_matmul_weight_bytes(CFG, 1) == streamed - experts * 60 / 64
    assert F.decode_matmul_weight_bytes(CFG, 16) == streamed
    assert F.decode_matmul_weight_bytes(CFG, 50) == streamed
    assert F.decode_moe_weight_bytes(CFG, 50) == experts
    assert F.decode_moe_weight_bytes(CFG, 2) == experts / 8
    # the convolution layers: two projections and the taps once, a slot's
    # two rows of state read and written, eight rows of activations
    conv_w = table["conv/w_in"] + table["conv/w_out"] + taps
    assert table["conv/w_in"] == 12 * (D * 3 * D + 4 * 3 * D)
    state = 12 * 2 * D * 2  # 98,304 B a slot
    assert F.decode_conv_bytes(CFG, 50, 2) == conv_w + 50 * (
        2 * state + 12 * 8 * D * 2)
    # pages 8 KB a token (4 attention layers), the state read and written
    row = 2 * KH * HD * 2
    assert 4 * row == 8 * 1024
    ctx = [1000, 24]
    want = (streamed - experts * 56 / 64 + taps + 2 * 2 * state
            + row * 4 * (1001 + 25))  # two slots route to 8 experts a layer
    assert F.decode_step_bytes(CFG, ctx, 2) == want


def test_prefill_flops_count_routed_pairs_not_held_experts():
    per_token = (4 * ATTN + 12 * CONV + 2 * 3 * D * MD
                 + 14 * (D * E + EXPERT * K))
    assert F.matmul_params_per_token(CFG) == per_token
    got = F.prefill_chunk_flops(CFG, 512, 512)
    seen = 512 * 512 + 512 * 513 // 2
    want = (2 * 512 * per_token + 4 * H * HD * 4 * seen
            + 2 * 512 * 12 * TAPS * D + 2 * D * V)
    assert got == want


def test_regions_are_the_programs():
    from substratus_tpu.ops import scopes

    assert set(F.SCOPES) == set(scopes.CONV)
    assert set(F.MATMUL_SCOPES) <= set(scopes.EVERY)
    assert {"conv.in", "conv.out", "moe.experts", "lm_head"} <= set(
        F.MATMUL_SCOPES)
    assert "conv.state" not in F.MATMUL_SCOPES  # it streams no weight


@pytest.fixture(scope="module")
def model():
    cfg = small()
    return cfg, W.make_weights(F.leaf_table(cfg), 2**31 + 3)


def test_reference_matches_the_programs_forward(model):
    """The program in float32 against the reference on the same seeded int8
    weights: summation order alone. (In bfloat16 a rounding flips an
    expert's choice now and then and moves a logit by tenths: PERF.md.)"""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import system
    from substratus_tpu.models import lfm2_moe

    cfg, w = model
    tokens = T.prompt_tokens(5, 0, 48, cfg["vocab_size"])
    want = np.asarray(M.reference_of(cfg).logits_at(
        w, cfg, tokens, list(range(48)), pad_to=16))
    mcfg = system.model_config(F, cfg).replace(dtype=jnp.float32)
    assert mcfg.held_experts == (0, 64) and mcfg.n_layers == 8
    with jax.default_matmul_precision("highest"):
        got, _ = lfm2_moe.forward(
            system._wrap(w), jnp.asarray([tokens], jnp.int32), mcfg)
    assert np.max(np.abs(np.asarray(got[0]) - want)) < 1e-4
    assert np.std(want) > 0.3  # the logits are not degenerate


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
        # the program's counters reach the readers: some of the 64 experts
        # a layer are chosen each step, some chunks begin from carried rows
        per_layer = {k: v["value"]
                     for k, v in result["counts"]["per_layer"].items()}
        assert 5.0 < per_layer["moe_decode_experts_touched_share"] < 100.0
        assert 0.0 < per_layer["conv_chunks_resumed_share"] < 60.0
        assert result["counts"]["preemptions"] == 0
        return
    assert result["correct"] is False
    assert n["precision_other_than_stated"]["value"] == 1.0
    if control == "int4":  # fails a gap's limit by itself, types aside
        assert (n["gap_max"]["value"] > n["gap_max"]["limit"]
                or n["gap_mean"]["value"] > n["gap_mean"]["limit"]), n


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
           "mix": {}, "chips": 1, "device": {"kind": "TPU v5 lite"},
           "records": [], "traced": (0.0, 1.0), "w0": 0.0, "w1": 1.0,
           "counters": {"stats": {"preemptions": 0}}, "trace": None,
           "rehearse": False}
    assert M.layer_reader(name)(run) is None
    assert M.layer_reader(name)(dict(run, family=F)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_are_listed_for_the_new_cell_alone(name):
    m = next(x for x in MAN["per_layer"] if x["name"] == name)
    assert m["workloads"] == [CELL] and m["moves"] == "itl_p50_ms"
    assert (m["source"] == "device_trace") == name.startswith("decode_")


def test_counter_readers_read_the_engines_deltas():
    run = {"config": CFG, "family": F, "rehearse": False,
           "counters": {"stats": {
               "moe_decode_steps": 10, "moe_decode_experts_touched": 8064,
               "conv_chunks_sum": 40, "conv_chunks_resumed_sum": 12}}}
    # 8,064 of 10 steps x 14 layers x 64 experts
    assert M.layer_reader("moe_decode_experts_touched_share")(run) == 90.0
    assert M.layer_reader("conv_chunks_resumed_share")(run) == 30.0


def test_the_mix_is_paced_and_its_sizes_are_the_issues():
    mix = M.traffic_of("assist")
    assert mix["loop"] == "open" and mix["engine"] == {
        "max_batch": 64, "max_seq_len": 2048, "max_prefill_len": 512,
        "kv_pool_tokens": 98304}
    assert mix["check_requests"] == 4 and mix["rate_why"] and mix["who"]
    pairs = T.block_pairs(mix)
    prompts = sorted(p for p, _ in pairs)
    outs = sorted(o for _, o in pairs)
    assert 128 <= prompts[0] and prompts[-1] <= 1152
    assert 192 <= outs[0] and outs[-1] <= 768
    assert all(p % 16 == 0 for p in prompts) and all(o % 8 == 0 for o in outs)
    assert 340 < float(np.median(prompts)) < 430  # log-uniform: median 384
    assert 350 < float(np.median(outs)) < 420
    assert max(prompts) + max(outs) < mix["engine"]["max_seq_len"]
    # about two prompts in five cross a chunk boundary, one in twenty two
    over = [p > 512 for p in prompts]
    assert 0.3 < sum(over) / len(over) < 0.45
    assert 0 < sum(p > 1024 for p in prompts) <= len(prompts) // 10
    # nothing is preempted: the pool holds every slot at 1,536 tokens
    assert mix["engine"]["kv_pool_tokens"] == 64 * 1536
