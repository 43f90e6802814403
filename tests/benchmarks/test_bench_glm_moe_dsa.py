"""The GLM-5 family's files (benchmarks/families/glm_moe_dsa.py, its
reference, the configuration glm-5-ep32-l13 and the mix repoqa) as the
other families' tests walk theirs: the published widths against the
catalog, the weight tree against the program's, the counts against
hand-computed bytes and FLOPs, the reference against the program's forward
where the selection bites, the rehearsal cell end to end, the comparison of
types against both controls and an int8 index pool, and the new readers on
runs that have nothing for them to read."""
import json
import math
import os

import numpy as np
import pytest

from benchmarks.harness import counts, manifest as M
from benchmarks.harness import traffic as T
from benchmarks.harness import weights as W

MAN = M.load()
NAME, CELL = "glm-5-ep32-l13", "glm-5.repoqa"
CFG = json.load(open(M.BENCH / "configs" / f"{NAME}.json"))
F = M.family_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

D, H, RQ, RKV, DN, DR, DV, MD, MM, V, L, E, EH, K, HI, DI, TOPK = (
    6144, 64, 2048, 512, 192, 64, 256, 12288, 2048, 19360, 13, 256, 8, 8,
    32, 128, 2048)
# W_DQ + W_UQ + W_DKV + W_UKV + W_O: 165.0 M a layer
MLA = (D * RQ + RQ * H * (DN + DR) + D * (RKV + DR)
       + RKV * H * (DN + DV) + H * DV * D)
INDEX = RQ * HI * DI + D * DI + D * HI  # W_IQ + W_IK + W_IW: 9.4 M a layer
EXPERT = 3 * D * MM  # 37.7 M
NEW_READERS = [
    "decode_index_ms", "decode_select_ms", "decode_index_hbm_share",
    "decode_sparse_attn_hbm_share", "dsa_rows_attended_share",
    "decode_moe_experts_hbm_share.glm5"]


def small(**over):
    cfg = dict(CFG, **{k: v for k, v in CFG["rehearse"].items() if k != "why"})
    cfg.update(over)
    return cfg


def test_dims_from_published_keys():
    s = F.dims(CFG)
    assert (s["D"], s["H"], s["rq"], s["rkv"], s["dn"], s["dr"], s["dv"],
            s["M"], s["Mm"], s["Ms"], s["V"], s["L"], s["E"], s["Eh"],
            s["first"], s["K"], s["G"], s["Gk"], s["Hi"], s["di"],
            s["topk"]) == (
        D, H, RQ, RKV, DN, DR, DV, MD, MM, MM, V, L, E, EH, 0, K, 1, 1, HI,
        DI, TOPK)
    assert s["mlp"] == ("dense",) * 3 + ("sparse",) * 10
    assert s["yarn"] is None and s["theta"] == 1e6
    assert F.softmax_scale(s) == 256 ** -0.5
    assert (MLA, INDEX) == (165_019_648, 9_371_648)


def test_config_file_keeps_published_widths_and_says_what_it_cut():
    entry = next(c for c in MAN["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert CFG["published"] == {**CFG["published"], "num_hidden_layers": 78,
                                "n_routed_experts": 256, "vocab_size": 154880,
                                "num_nextn_predict_layers": 1}
    assert CFG["num_hidden_layers"] >= CFG["first_k_dense_replace"] + 4
    assert CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CFG["published"]["vocab_size"]
    assert CFG["precision"] == {**CFG["precision"], "weights": "int8",
                                "activations": "bfloat16",
                                "kv_cache": "bfloat16"}
    assert CFG["layout"]["chips"] == M.cell(MAN, CELL)["chips"] == 1
    assert CFG["layout"]["experts_held"] == [0, 8]
    for key in ("block", "indexer", "indexer_left_out", "ties",
                "rotary_layout", "router", "shared_expert",
                "multi_token_prediction", "weights"):
        assert CFG["assumed"][key]
    assert "LEFT OUT" in CFG["assumed"]["indexer_left_out"]
    assert "LEFT OUT" in CFG["assumed"]["multi_token_prediction"]
    for name in CFG["reduced"]:
        assert not name.endswith(("_dim", "_rank", "_size")) or \
            name == "vocab_size"
    # the rehearsal's selection bites: contexts several times its top-k
    small_mix = M.traffic_of("repoqa")["rehearse"]
    assert small()["index_topk"] * 3 <= small_mix["prompt_len"]["lo"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_is_held_or_listed_as_reduced():
    row = next(json.loads(l) for l in open(CATALOG)
               if json.loads(l)["name"] == "GLM-5")
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
    assert (mcfg.n_group, mcfg.topk_group, mcfg.first_k_dense) == (1, 1, 3)
    assert mcfg.yarn is None and mcfg.softmax_scale == 256 ** -0.5
    assert (mcfg.index_n_heads, mcfg.index_head_dim, mcfg.index_topk,
            mcfg.index_norm_eps) == (HI, DI, TOPK, 1e-6)
    theirs = module.quant_contracting(mcfg)
    shapes = jax.eval_shape(lambda k: module.init_params(mcfg, k),
                            jax.random.key(0))
    table = F.leaf_table(CFG)
    assert len(table) == len(jax.tree.leaves(shapes))
    for path, leaf in table.items():
        assert tuple(W.at(theirs, path)) == tuple(leaf.contracting), path
        assert (leaf.kind == "int8") is bool(leaf.contracting), path
        assert tuple(W.at(shapes, path).shape) == tuple(leaf.shape), path
    assert table["layers/w_iq"].shape == (L, HI, DI, RQ)
    assert table["layers/ik_norm_bias"].kind == "bias"
    assert table["moe/router"].shape == (10, D, 256)  # the published width


def test_weight_bytes_are_the_issues_arithmetic():
    table = F.leaf_table(CFG)
    wb = counts.weight_bytes(table)
    assert wb["moe/w_gate"] == 10 * 8 * D * MM + 4 * 10 * 8 * MM
    assert wb["tok_embed"] == 2 * V * D
    assert 6.7e9 < sum(wb.values()) < 6.8e9  # the issue's 6.73 GB
    n = sum(math.prod(leaf.shape) for leaf in table.values())
    assert n == (L * (MLA + INDEX + 2 * D + RQ + RKV + 2 * DI)
                 + 3 * 3 * D * MD + 10 * (9 * EXPERT + D * E + E)
                 + 2 * V * D + D)


def test_decode_counts_are_what_the_step_must_move():
    wb = counts.weight_bytes(F.leaf_table(CFG))
    experts = sum(wb[n] for n in ("moe/w_gate", "moe/w_up", "moe/w_down"))
    shared = sum(wb[n] for n in ("moe/shared_gate", "moe/shared_up",
                                 "moe/shared_down"))
    kept = sum(wb[n] for n in (
        "tok_embed", "layers/attn_norm", "layers/mlp_norm",
        "layers/q_a_norm", "layers/kv_a_norm", "layers/ik_norm",
        "layers/ik_norm_bias"))
    index = sum(wb[n] for n in ("layers/w_iq", "layers/w_ik", "layers/w_iw"))
    streamed = sum(wb.values()) - kept
    assert F.decode_matmul_weight_bytes(CFG, 4) == streamed
    assert F.decode_moe_weight_bytes(CFG, 4) == experts + shared
    # a token keeps 576 values and a key of 128 a layer
    assert (F.latent_row_bytes(CFG), F.index_key_bytes(CFG)) == (1152, 256)
    # the index reads every live key and its own weights; the attention
    # reads min(k, context) rows a slot, whatever the context
    assert F.index_decode_bytes(CFG, 68_800) == L * 68_800 * 256 + index
    ctx = [17_200, 17_300, 100, 2_048]
    assert F.attended(CFG, ctx) == 2048 + 2048 + 100 + 2048
    assert F.sparse_decode_bytes(CFG, ctx) == L * 6244 * 1152
    assert F.latent_decode_bytes(CFG, sum(ctx)) == L * sum(ctx) * 1152
    want = (streamed + 2 * D * 4 + L * sum(ctx) * 256 + L * 6244 * 1152
            + L * 4 * (1152 + 256))
    assert F.decode_step_bytes(CFG, ctx, 2) == want


def test_prefill_flops_count_the_index_and_each_querys_set():
    per_token = (L * (MLA + INDEX) + 3 * 3 * D * MD
                 + 10 * (D * E + EXPERT + EXPERT * K * EH / E))
    assert F.matmul_params_per_token(CFG) == per_token
    seen = 512 * 5120 + 512 * 513 // 2
    # behind 5,120 tokens every query's set is full
    assert F.prefill_chunk_flops(CFG, 512, 5120) == (
        2 * 512 * per_token + L * 2 * HI * DI * seen
        + 2 * L * H * (DN + DR + DV) * 512 * TOPK + 2 * D * V)
    # at offset 0 a set is everything seen up to 2,048
    assert F.latent_chunk_flops(CFG, 512, 512) == L * (
        2 * H * 512 * (512 * 513 // 2) + 2 * RKV * H * (DN + DV) * 512)
    assert F.index_chunk_flops(CFG, 512, 5632) == L * 2 * HI * DI * seen


def test_regions_are_the_programs():
    from substratus_tpu.ops import scopes

    assert set(F.SCOPES) == {scopes.MOE_SHARED, *scopes.LATENT,
                             *scopes.INDEXED}
    assert set(F.MATMUL_SCOPES) <= set(scopes.EVERY)
    assert "attn.index" in F.MATMUL_SCOPES
    assert "attn.select" not in F.MATMUL_SCOPES


@pytest.fixture(scope="module")
def model():
    cfg = small()
    return cfg, W.make_weights(F.leaf_table(cfg), 2**31 + 3)


def test_reference_matches_the_programs_forward(model):
    """The program in float32 against the reference on the same seeded int8
    weights at the rehearsal size (2 dense + 4 sparse layers, 4 of 16
    experts held, the 16 best of up to 96 rows a query): summation order
    alone, the sets equal."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import system
    from substratus_tpu.models import deepseek_v3

    cfg, w = model
    tokens = T.prompt_tokens(5, 0, 96, cfg["vocab_size"])
    sets = []
    want = np.asarray(M.reference_of(cfg).logits_at(
        w, cfg, tokens, list(range(96)), pad_to=32, sets_out=sets))
    assert len(sets) == 6 and sets[0][95].sum() == 16
    mcfg = system.model_config(F, cfg).replace(dtype=jnp.float32)
    assert mcfg.held_experts == (0, 4) and mcfg.n_experts == 16
    assert (mcfg.index_n_heads, mcfg.index_topk) == (2, 16)
    assert deepseek_v3.layer_plan(mcfg) == (2, 1, 4)
    with jax.default_matmul_precision("highest"):
        got, _ = deepseek_v3.forward(
            system._wrap(w), jnp.asarray([tokens], jnp.int32), mcfg)
    assert np.max(np.abs(np.asarray(got[0]) - want)) < 1e-4
    assert np.std(want) > 0.3  # the logits are not degenerate


def test_the_counters_reach_their_reader():
    """`dsa_rows_attended_share` is the ratio of the engine's two sums,
    on the chip and in a rehearsal alike (the cell's own rehearsal, with
    `correct` and the contract's line, is
    test_bench_rehearse.py::test_rehearsal_prints_the_contract_line[*-glm-5.repoqa];
    the engine's counting tests/test_glm_moe_dsa.py's)."""
    read = M.layer_reader("dsa_rows_attended_share")
    stats = {"dsa_rows_live_sum": 68_800, "dsa_rows_attended_sum": 8_192,
             "dsa_selections": 52}
    for rehearse in (False, True):
        got = read({"counters": {"stats": stats}, "rehearse": rehearse})
        assert math.isclose(got, 100 * 8_192 / 68_800)
    assert read({"counters": {"stats": {}}, "rehearse": False}) is None


def test_the_comparison_of_types_fails_the_controls_and_an_int8_key_pool():
    """What `precision_found` reads off the engine is what the file states
    (int8 matmul weights, the indexer's among them; bfloat16 activations;
    both arrays of the pool bfloat16), and `check.compare` counts every
    departure: int8 activations, int4 weights, and an index pool narrowed
    to int8 under the name the harness reads."""
    import jax.numpy as jnp

    from benchmarks.harness import check, system

    cfg = small()
    table = F.leaf_table(cfg)
    sizes = {"max_batch": 2, "max_seq_len": 64, "max_prefill_len": 16,
             "kv_pool_tokens": 256}

    class Ref:
        @staticmethod
        def served_gaps(*a):
            return np.zeros(3)

    def departures(found):
        sample = [type("R", (), {"prompt": [1], "sink": type(
            "S", (), {"ids": [1]})()})()]
        out = check.compare(Ref, None, cfg, sample, cfg["correct"],
                            stated=CFG["precision"], found=found)
        return out["correct"], out["numbers"][
            "precision_other_than_stated"]["value"]

    engine = system.build_engine(F, cfg, sizes, W.make_weights(table, 11),
                                 None)
    found = system.precision_found(engine, table)
    assert found == {k: CFG["precision"][k] for k in found}
    assert departures(found) == (True, 0.0)
    assert engine.cache["k"].shape[0] == engine.cache["v"].shape[0] == 6
    assert engine.cache["v"].shape[3:] == (1, cfg["index_head_dim"])
    engine.cache["v"] = engine.cache["v"].astype(jnp.int8)
    narrowed = system.precision_found(engine, table)
    assert narrowed["kv_cache"] == "bfloat16+int8"
    assert departures(narrowed) == (False, 1.0)
    w8a8 = system.build_engine(F, cfg, sizes, W.make_weights(table, 11),
                               None, "w8a8")
    assert departures(system.precision_found(w8a8, table)) == (False, 1.0)
    int4 = system.build_engine(
        F, cfg, sizes,
        system.lower_weights(W.make_weights(table, 11), table), None, "int4")
    assert departures(system.precision_found(int4, table)) == (False, 1.0)
    with pytest.raises(ValueError, match="int8"):
        system.build_engine(F, cfg, sizes, W.make_weights(table, 11), None,
                            "int8kv")


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
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "itl_p50_ms", name
    for name in ("decode_index_hbm_share", "decode_sparse_attn_hbm_share"):
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["source"] == "device_trace"
    assert by_name["dsa_rows_attended_share"]["better"] == "lower"
    # appended: the entries this PR adds are the lists' last
    assert [m["name"] for m in MAN["per_layer"][-6:]] == NEW_READERS
    assert MAN["configs"][-1]["name"] == NAME
    assert MAN["workloads"][-1]["name"] == CELL


def test_the_mix_is_a_closed_loop_of_equal_requests():
    mix = M.traffic_of("repoqa")
    sizes = mix["engine"]
    prompt, reply = mix["prompt_len"]["lo"], mix["output_len"]["lo"]
    assert mix["loop"] == "closed"
    assert mix["clients"] == sizes["max_batch"] + 1 == mix["block"] + 1
    assert set(T.block_pairs(mix)) == {(prompt, reply)}
    assert prompt % 512 == 0 and sizes["max_prefill_len"] == 512
    assert T.prefill_buckets([prompt], 512) == [512]
    assert prompt + reply <= sizes["max_seq_len"]
    # nothing is preempted: the pool holds every slot at its longest
    assert sizes["kv_pool_tokens"] >= sizes["max_batch"] * (prompt + reply)
    # the selection does the cell's work: a context is many sets long
    assert prompt >= 4 * CFG["index_topk"]
