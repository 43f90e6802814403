"""The DeepSeek-V3 family's files (benchmarks/families/deepseek_v3.py, its
reference, the configuration dots-vlm1-inst-ep32-l16 and the mix docqa) as
the other families' tests walk theirs: the published widths against the
catalog, the weight tree against the program's, the counts against
hand-computed bytes and FLOPs, the reference against the program's
forward, the rehearsal cell end to end with the controls that must fail,
the engine holding the stated types, and the new readers on runs that have
nothing for them to read."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks.harness import counts, manifest as M
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W

MAN = M.load()
NAME, CELL = "dots-vlm1-inst-ep32-l16", "dots-vlm1.docqa"
CFG = json.load(open(M.BENCH / "configs" / f"{NAME}.json"))
F = M.family_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

D, H, RQ, RKV, DN, DR, DV, MD, MM, V, L, E, EH, K = (
    7168, 128, 1536, 512, 128, 64, 128, 18432, 2048, 16160, 16, 256, 8, 8)
# W_DQ + W_UQ + W_DKV + W_UKV + W_O: 187.1 M a layer
MLA = (D * RQ + RQ * H * (DN + DR) + D * (RKV + DR)
       + RKV * H * (DN + DV) + H * DV * D)
EXPERT = 3 * D * MM  # 44.0 M
# read in the cell: listed in BENCHMARK.json with the cell as their workloads
LISTED_READERS = [
    "decode_latent_attn_mxu_share", "decode_latent_attn_hbm_share",
    "decode_mla_proj_ms", "decode_moe_experts_hbm_share.ep32",
    "moe_routed_here_share.ep32", "sched_host_work_ms.docqa",
    "moe_pairs_per_expert_max_over_mean.ep32"]
# files without an entry: a cohort of equal requests prefills outside the
# traced 3 s, so the cell's trace holds no chunk for them to read
UNLISTED_READERS = ["chunk_latent_attn_ms", "chunk_latent_attn_mxu_share"]
NEW_READERS = LISTED_READERS + UNLISTED_READERS


def small(**over):
    cfg = dict(CFG, **{k: v for k, v in CFG["rehearse"].items() if k != "why"})
    cfg.update(over)
    return cfg


def test_dims_from_published_keys():
    s = F.dims(CFG)
    assert (s["D"], s["H"], s["rq"], s["rkv"], s["dn"], s["dr"], s["dv"],
            s["M"], s["Mm"], s["Ms"], s["V"], s["L"], s["E"], s["Eh"],
            s["first"], s["K"], s["G"], s["Gk"]) == (
        D, H, RQ, RKV, DN, DR, DV, MD, MM, MM, V, L, E, EH, 0, K, 8, 4)
    assert s["mlp"] == ("dense",) * 3 + ("sparse",) * 13
    assert (s["Ld"], s["Ls"]) == (3, 13)
    assert s["yarn"] == (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert math.isclose(F.softmax_scale(s), 192 ** -0.5 * 1.3689 ** 2,
                        rel_tol=1e-4)
    assert MLA == 187_105_280


def test_config_file_keeps_published_widths_and_says_what_it_cut():
    entry = next(c for c in MAN["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert CFG["published"] == {**CFG["published"], "num_hidden_layers": 61,
                                "n_routed_experts": 256, "vocab_size": 129280,
                                "num_nextn_predict_layers": 1}
    # the floors of a cut: the leading dense layers once and four that
    # follow, eight routed experts, an eighth of the vocabulary
    assert CFG["num_hidden_layers"] >= CFG["first_k_dense_replace"] + 4
    assert CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CFG["published"]["vocab_size"]
    assert CFG["precision"] == {**CFG["precision"], "weights": "int8",
                                "activations": "bfloat16",
                                "kv_cache": "bfloat16"}
    assert CFG["layout"]["chips"] == M.cell(MAN, CELL)["chips"] == 1
    assert CFG["layout"]["experts_held"] == [0, 8]
    for key in ("block", "rotary_layout", "router", "shared_expert",
                "multi_token_prediction", "vision_tower", "weights"):
        assert CFG["assumed"][key]
    assert "LEFT OUT" in CFG["assumed"]["vision_tower"]
    for name in CFG["reduced"]:
        assert not name.endswith(("_dim", "_rank", "_size")) or \
            name == "vocab_size"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_held_or_listed_as_reduced():
    row = next(json.loads(l) for l in open(CATALOG)
               if json.loads(l)["name"] == "dots.vlm1.inst")
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
    assert mcfg.held_experts == (0, 8) and mcfg.n_experts == 256
    assert (mcfg.n_group, mcfg.topk_group, mcfg.first_k_dense) == (8, 4, 3)
    assert mcfg.yarn == (40.0, 4096, 32.0, 1.0)
    assert math.isclose(mcfg.softmax_scale, F.softmax_scale(F.dims(CFG)))
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
    assert table["moe/router"].shape == (13, D, 256)  # the published width


def test_weight_bytes_are_the_issues_arithmetic():
    table = F.leaf_table(CFG)
    wb = counts.weight_bytes(table)
    assert wb["moe/w_gate"] == 13 * 8 * D * MM + 4 * 13 * 8 * MM
    assert wb["tok_embed"] == 2 * V * D
    assert 9.6e9 < sum(wb.values()) < 9.8e9  # the issue's 9.7 GB
    n = sum(math.prod(leaf.shape) for leaf in table.values())
    assert n == (L * (MLA + 2 * D + RQ + RKV) + 3 * 3 * D * MD
                 + 13 * (9 * EXPERT + D * E + E) + 2 * V * D + D)


def test_decode_counts_are_the_least_work():
    wb = counts.weight_bytes(F.leaf_table(CFG))
    experts = sum(wb[n] for n in ("moe/w_gate", "moe/w_up", "moe/w_down"))
    shared = sum(wb[n] for n in ("moe/shared_gate", "moe/shared_up",
                                 "moe/shared_down"))
    norms = sum(wb[n] for n in ("layers/attn_norm", "layers/mlp_norm",
                                "layers/q_a_norm", "layers/kv_a_norm"))
    streamed = sum(wb.values()) - wb["tok_embed"] - norms
    # any slot can route to all 8 held experts
    assert F.decode_matmul_weight_bytes(CFG, 1) == streamed
    assert F.decode_matmul_weight_bytes(CFG, 11) == streamed
    assert F.decode_moe_weight_bytes(CFG, 11) == experts + shared
    # a token keeps 576 values a layer: 18,432 bytes over 16 layers
    assert F.latent_row_bytes(CFG) == 1152
    assert F.latent_decode_bytes(CFG, 1) == 18432
    assert F.latent_decode_bytes(CFG, 118_000) == 118_000 * 18432
    # a head and token, 576 multiply-adds of score and 512 of read-out:
    # 242 FLOPs a byte, at the v5e's ridge (197e12 / 819e9 = 240.5)
    assert F.latent_decode_flops(CFG, 1) == 16 * 2 * 128 * (576 + 512)
    ridge = F.latent_decode_flops(CFG, 1) / F.latent_decode_bytes(CFG, 1)
    assert 241 < ridge < 243
    ctx = [10_000, 24]
    want = (streamed + 2 * D * 2 + 18432 * (10_000 + 24) + 18432 * 2)
    assert F.decode_step_bytes(CFG, ctx, 2) == want


def test_prefill_flops_count_routed_pairs_and_the_expanded_form():
    per_token = (L * MLA + 3 * 3 * D * MD
                 + 13 * (D * E + EXPERT + EXPERT * K * EH / E))
    assert F.matmul_params_per_token(CFG) == per_token
    seen = 512 * 5120 + 512 * 513 // 2
    assert F.prefill_chunk_flops(CFG, 512, 5120) == (
        2 * 512 * per_token + 2 * L * H * (DN + DR + DV) * seen + 2 * D * V)
    # the chunk's attention: the same pairs, and its own 512 latents
    # through W_UKV once
    assert F.latent_chunk_flops(CFG, 512, 5120 + 512) == L * (
        2 * H * (DN + DR + DV) * seen + 2 * RKV * H * (DN + DV) * 512)
    # a chunk at offset 0 sees itself alone
    assert F.latent_chunk_flops(CFG, 512, 512) == L * (
        2 * H * 320 * (512 * 513 // 2) + 2 * RKV * H * 256 * 512)


def test_regions_are_the_programs():
    from substratus_tpu.ops import scopes

    assert set(F.SCOPES) == {scopes.MOE_SHARED, *scopes.LATENT}
    assert set(F.MATMUL_SCOPES) <= set(scopes.EVERY)
    assert {"attn.absorb", "attn.expand", "moe.shared"} <= set(F.MATMUL_SCOPES)


@pytest.fixture(scope="module")
def model():
    cfg = small()
    return cfg, W.make_weights(F.leaf_table(cfg), 2**31 + 3)


def test_reference_matches_the_programs_forward(model):
    """The program in float32 against the reference on the same seeded int8
    weights at the rehearsal size (2 dense + 4 sparse layers, 4 of 16
    experts held under a group limit, YaRN): summation order alone."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import system
    from substratus_tpu.models import deepseek_v3

    cfg, w = model
    tokens = T.prompt_tokens(5, 0, 48, cfg["vocab_size"])
    want = np.asarray(M.reference_of(cfg).logits_at(
        w, cfg, tokens, list(range(48)), pad_to=16))
    mcfg = system.model_config(F, cfg).replace(dtype=jnp.float32)
    assert mcfg.held_experts == (0, 4) and mcfg.n_experts == 16
    assert deepseek_v3.layer_plan(mcfg) == (2, 1, 4)
    with jax.default_matmul_precision("highest"):
        got, _ = deepseek_v3.forward(
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
        # the program's counters reach the readers: a quarter of the pairs
        # lands on the 4 held of 16 (the group limit favours no group),
        # nothing is preempted, nothing compiles in the window
        per_layer = {k: v["value"]
                     for k, v in result["counts"]["per_layer"].items()}
        assert 15.0 < per_layer["moe_routed_here_share.ep32"] < 35.0
        assert result["counts"]["preemptions"] == 0
        assert result["counts"]["compiles_in_window"] == 0
        return
    assert result["correct"] is False
    assert n["precision_other_than_stated"]["value"] == 1.0
    if control == "int4":  # fails a gap's limit by itself, types aside
        assert (n["gap_max"]["value"] > n["gap_max"]["limit"]
                or n["gap_mean"]["value"] > n["gap_mean"]["limit"]), n


def test_the_engine_holds_the_stated_types():
    """What `precision_found` reads off the engine is what the file states:
    int8 matmul weights, bfloat16 activations, a bfloat16 pool whose `v`
    holds no layer."""
    import jax.numpy as jnp

    from benchmarks.harness import system

    cfg = small()
    table = F.leaf_table(cfg)
    engine = system.build_engine(
        F, cfg, {"max_batch": 2, "max_seq_len": 64, "max_prefill_len": 16,
                 "kv_pool_tokens": 256},
        W.make_weights(table, 11), None)
    found = system.precision_found(engine, table)
    assert found == {k: CFG["precision"][k] for k in found}
    assert engine.cache["k"].dtype == engine.cache["v"].dtype == jnp.bfloat16
    assert engine.cache["v"].shape[0] == 0
    assert engine.cache["k"].shape[0] == cfg["num_hidden_layers"]


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


def test_the_new_metrics_name_the_new_cell_alone():
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in LISTED_READERS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "itl_p50_ms", name
    for name in ("decode_latent_attn_mxu_share",
                 "decode_latent_attn_hbm_share"):
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["source"] == "device_trace"
    # a traced run whose line lacks a listed metric is refused
    assert not set(UNLISTED_READERS) & set(by_name)


def test_the_mix_is_a_closed_loop_and_its_sizes_are_the_issues():
    mix = M.traffic_of("docqa")
    assert (mix["loop"], mix["clients"], mix["block"]) == ("closed", 13, 12)
    assert mix["engine"] == {"max_batch": 12, "max_seq_len": 14336,
                             "max_prefill_len": 512,
                             "kv_pool_tokens": 163840}
    # the end of ISSUE 40's list: every prompt 10,240, every reply 1,024 (the
    # first mix's twelve prompt lengths spread `itl_p50_ms` by 2.3 %), then
    # 13 clients
    assert set(T.block_pairs(mix)) == {(10240, 1024)}
    assert 10240 + 1024 < mix["engine"]["max_seq_len"]
    # 20 whole chunks of 512: one bucket, no padding
    assert T.prefill_buckets([10240], 512) == [512]
    # nothing is preempted: the pool holds every slot at its longest
    assert mix["engine"]["kv_pool_tokens"] >= 12 * (10240 + 1024)
