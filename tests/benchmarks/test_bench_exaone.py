"""The EXAONE-MoE family's files (benchmarks/families/exaone_moe.py, its
reference, the configuration k-exaone-236b-a23b-ep8 and the mix
reason-mixed) as the other families' tests walk theirs: the published widths
against the catalog, the weight tree against the program's, the counts
against hand-computed bytes and FLOPs, the reference against the program's
forward, the controls that must fail, and the new readers on runs that
have nothing for them to read."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks.harness import counts, manifest as M
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W

MAN = M.load()
NAME, CELL = "k-exaone-236b-a23b-ep8", "k-exaone-236b.reason-mixed"
CFG = json.load(open(M.BENCH / "configs" / f"{NAME}.json"))
F = M.family_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

D, H, KH, HD, MD, MM, V, L, E, EH, K, WIN = (6144, 64, 8, 128, 18432, 2048,
                                             19200, 12, 128, 16, 8, 128)
ATTN = D * H * HD + 2 * D * KH * HD + H * HD * D  # 113,246,208
EXPERT = 3 * D * MM                               # 37,748,736


def small(**over):
    cfg = dict(CFG, **{k: v for k, v in CFG["rehearse"].items() if k != "why"})
    cfg.update(over)
    return cfg


def test_dims_from_published_keys():
    s = F.dims(CFG)
    assert (s["D"], s["H"], s["KH"], s["hd"], s["M"], s["Mm"], s["Ms"],
            s["V"], s["L"], s["E"], s["Eh"], s["first"], s["K"], s["W"]) == (
        D, H, KH, HD, MD, MM, MM, V, L, E, EH, 0, K, WIN)
    assert s["attn"] == ("sliding_attention",) * 3 + ("full_attention",) \
        + s["attn"][4:] and len(s["attn"]) == 12
    assert (s["Lw"], s["Lg"], s["Ld"], s["Ls"]) == (9, 3, 1, 11)
    assert s["mlp"][0] == "dense" and set(s["mlp"][1:]) == {"sparse"}


def test_config_file_keeps_published_widths_and_says_what_it_cut():
    entry = next(c for c in MAN["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"],
            CFG["intermediate_size"], CFG["moe_intermediate_size"],
            CFG["num_experts_per_tok"], CFG["sliding_window"],
            CFG["published"]["num_experts"]) == (6144, 64, 8, 128, 18432,
                                                 2048, 8, 128, 128)
    assert CFG["published"]["num_hidden_layers"] == 48
    assert CFG["published"]["vocab_size"] == 153600
    # the floors of a cut: a whole period and four layers after the dense
    # one, eight routed experts, an eighth of the vocabulary
    assert CFG["num_hidden_layers"] >= 1 + 4 and CFG["num_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CFG["published"]["vocab_size"]
    assert CFG["precision"] == {**CFG["precision"], "weights": "int8",
                                "activations": "bfloat16",
                                "kv_cache": "bfloat16"}
    assert CFG["layout"]["chips"] == M.cell(MAN, CELL)["chips"] == 1
    assert CFG["layout"]["experts_held"] == [0, 16]
    for key in ("qk_norm", "rotary", "block_norms", "router",
                "multi_token_prediction"):
        assert CFG["assumed"][key]
    for name in CFG["reduced"]:
        assert not name.endswith(("_dim", "_rank", "_size")) or \
            name == "vocab_size"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_held_or_listed_as_reduced():
    row = next(json.loads(l) for l in open(CATALOG)
               if json.loads(l)["name"] == "K-EXAONE-236B-A23B")
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
    assert mcfg.held_experts == (0, 16) and mcfg.n_experts == 128
    theirs = module.quant_contracting(mcfg)
    shapes = jax.eval_shape(lambda k: module.init_params(mcfg, k),
                            jax.random.key(0))
    table = F.leaf_table(CFG)
    assert len(table) == len(jax.tree.leaves(shapes))
    for path, leaf in table.items():
        assert tuple(W.at(theirs, path)) == tuple(leaf.contracting), path
        assert (leaf.kind == "int8") is bool(leaf.contracting), path
        assert leaf.stacked is (path.split("/")[0] in ("layers", "dense",
                                                       "moe")), path
        assert tuple(W.at(shapes, path).shape) == tuple(leaf.shape), path
    assert table["moe/router_bias"].kind == "bias"
    assert table["moe/router"].shape == (11, D, 128)  # the published width


def test_weight_bytes_are_the_issues_arithmetic():
    wb = counts.weight_bytes(F.leaf_table(CFG))
    assert wb["moe/w_gate"] == 11 * 16 * D * MM + 4 * 11 * 16 * MM
    assert wb["tok_embed"] == 2 * V * D
    total = sum(wb.values())
    assert 9.05e9 < total < 9.2e9  # 0.453 + 11 x 0.755 + 0.354 GB
    n = sum(math.prod(leaf.shape) for leaf in F.leaf_table(CFG).values())
    assert n == (L * (ATTN + 2 * D + 2 * HD) + 3 * D * MD
                 + 11 * (17 * EXPERT + D * E + E) + 2 * V * D + D)


def test_decode_bytes_count_the_least_work():
    table = counts.weight_bytes(F.leaf_table(CFG))
    experts = sum(table[n] for n in ("moe/w_gate", "moe/w_up", "moe/w_down"))
    shared = sum(table[n] for n in ("moe/shared_gate", "moe/shared_up",
                                    "moe/shared_down"))
    norms = sum(table[n] for n in ("layers/attn_norm", "layers/mlp_norm",
                                   "layers/q_norm", "layers/k_norm"))
    streamed = sum(table.values()) - table["tok_embed"] - norms
    # one slot can route to 8 of the 16 held experts, two or more to all
    assert F.decode_matmul_weight_bytes(CFG, 1) == streamed - experts / 2
    assert F.decode_matmul_weight_bytes(CFG, 50) == streamed
    assert F.decode_moe_weight_bytes(CFG, 50) == experts + shared
    assert F.decode_moe_weight_bytes(CFG, 1) == experts / 2 + shared
    # a global layer's whole context, a window layer's newest 128 rows
    row = 2 * KH * HD * 2  # 4 KB a token and layer
    ctx = [1000, 24]
    want = (streamed + 2 * D * 2
            + row * (3 * 1024 + 9 * (128 + 24) + 2 * 12))
    assert F.decode_step_bytes(CFG, ctx, 2) == want
    # 12 KB a token in the pool, not 48
    assert 3 * row == 12 * 1024


def test_prefill_flops_count_routed_pairs_not_held_experts():
    per_token = (L * ATTN + 3 * D * MD
                 + 11 * (D * E + EXPERT + EXPERT * K * EH / E))
    assert F.matmul_params_per_token(CFG) == per_token
    got = F.prefill_chunk_flops(CFG, 512, 1024)
    seen_all = 512 * 1024 + 512 * 513 // 2
    want = (2 * 512 * per_token
            + 4 * H * HD * (3 * seen_all + 9 * 512 * 128) + 2 * D * V)
    assert got == want
    # every token through every held expert would be 16 experts a token
    every = 2 * 512 * 11 * EXPERT * (EH - K * EH / E)
    assert every / got > 2.0


def test_regions_are_the_programs():
    from substratus_tpu.ops import scopes

    assert set(F.SCOPES) == set(scopes.EXTRA)
    assert set(F.MATMUL_SCOPES) <= set(scopes.EVERY)
    assert {"moe.shared", "moe.experts", "moe.router"} <= set(F.MATMUL_SCOPES)


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
    from substratus_tpu.models import exaone_moe

    cfg, w = model
    tokens = T.prompt_tokens(5, 0, 48, cfg["vocab_size"])
    want = np.asarray(M.reference_of(cfg).logits_at(
        w, cfg, tokens, list(range(48)), pad_to=16))
    mcfg = system.model_config(F, cfg).replace(dtype=jnp.float32)
    assert mcfg.held_experts == (0, 4) and mcfg.n_experts == 16
    with jax.default_matmul_precision("highest"):
        got, _ = exaone_moe.forward(
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
        # the program's counters reach the readers: a share of the pairs
        # lands on the 4 held of 16, nothing is preempted
        per_layer = {k: v["value"]
                     for k, v in result["counts"]["per_layer"].items()}
        assert 10.0 < per_layer["moe_routed_here_share"] < 45.0
        assert per_layer["moe_pairs_per_expert_max_over_mean"] >= 1.0
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


@pytest.mark.parametrize("name", [
    "decode_moe_experts_hbm_share", "decode_window_attn_ms",
    "moe_pairs_per_expert_max_over_mean", "moe_routed_here_share"])
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


def test_the_mix_is_paced_and_its_sizes_are_the_issues():
    mix = M.traffic_of("reason-mixed")
    assert mix["loop"] == "open" and mix["engine"] == {
        **mix["engine"], "max_batch": 64, "max_seq_len": 4096,
        "max_prefill_len": 512}
    pairs = T.block_pairs(mix)
    prompts = sorted(p for p, _ in pairs)
    outs = sorted(o for _, o in pairs)
    assert 256 <= prompts[0] and prompts[-1] <= 3072
    assert 192 <= outs[0] and outs[-1] <= 768
    assert all(p % 16 == 0 for p in prompts) and all(o % 8 == 0 for o in outs)
    assert 800 < float(np.median(prompts)) < 980  # log-uniform: median 887
    assert 350 < float(np.median(outs)) < 420
    assert max(prompts) + max(outs) < mix["engine"]["max_seq_len"]
    # nothing is preempted: the pool holds every slot at the mean context
    assert mix["engine"]["kv_pool_tokens"] >= 64 * 1600
