"""counts.py and the llama family's counts against hand-computed bytes and
FLOPs for both configurations; the peak table refuses an unlisted device."""
import json

import pytest

from benchmarks.harness import counts, manifest as M, peaks

MAN = M.load()
def _config(name):
    return json.load(open(M.BENCH / "configs" / f"{name}.json"))


MISTRAL = _config("mistral-7b-instruct-v0.2")
MIXTRAL = _config("mixtral-8x7b-instruct-v0.1")
F = M.family_of(MISTRAL)  # both configurations name the one family
model_dims, leaf_table = F.dims, F.leaf_table

D, H, KH, HD, MLP, V, L = 4096, 32, 8, 128, 14336, 32000, 32
ATTN = D * H * HD + 2 * D * KH * HD + H * HD * D          # 41,943,040
DENSE_MLP = 3 * D * MLP                                   # 176,160,768


def test_dims_from_published_keys():
    assert model_dims(MISTRAL) == {"D": D, "H": H, "KH": KH, "hd": HD,
                                   "M": MLP, "V": V, "L": L, "E": 0, "K": 0}
    assert model_dims(MIXTRAL)["E"] == 8 and model_dims(MIXTRAL)["K"] == 2


def test_mistral_parameter_count():
    n = sum(
        int(__import__("math").prod(shape))
        for shape, *_ in leaf_table(MISTRAL).values()
    )
    # 7.24 B: the published size of Mistral-7B
    assert n == L * (ATTN + DENSE_MLP + 2 * D) + 2 * V * D + D == 7_241_732_096


def test_mistral_weight_bytes():
    wb = counts.weight_bytes(leaf_table(MISTRAL))
    assert wb["layers/wq"] == L * D * H * HD + 4 * L * H * HD
    assert wb["layers/wo"] == L * H * HD * D + 4 * L * D
    assert wb["layers/w_down"] == L * MLP * D + 4 * L * D
    assert wb["tok_embed"] == 2 * V * D
    assert wb["lm_head"] == D * V + 4 * V


def test_mistral_decode_step_bytes():
    ctx = [1000, 24]
    kv_row = L * 2 * KH * HD * 2  # 131,072 bytes a token
    weights = sum(b for n, b in counts.weight_bytes(leaf_table(MISTRAL)).items()
                  if n != "tok_embed")
    want = weights + 2 * D * 2 + kv_row * (1024 + 2)
    assert F.decode_step_bytes(MISTRAL, ctx, 2) == want
    assert 7.1e9 < weights < 7.2e9  # int8: about a byte a parameter


def test_mixtral_decode_streams_only_routable_experts():
    one = F.decode_step_bytes(MIXTRAL, [10], 2)
    full = F.decode_step_bytes(MIXTRAL, [10] * 32, 2)
    expert_bytes = sum(b for n, b in counts.weight_bytes(leaf_table(MIXTRAL)).items()
                       if n.split("/")[-1] in ("w_gate", "w_up", "w_down"))
    kv_row = L * 2 * KH * HD * 2
    assert full - one == pytest.approx(
        expert_bytes * 6 / 8 + 31 * (2 * D + 11 * kv_row))
    assert 46e9 < full < 48e9  # 47 GB of int8 weights


def test_matmul_params_per_token():
    assert F.matmul_params_per_token(MISTRAL) == L * (ATTN + DENSE_MLP)
    assert F.matmul_params_per_token(MIXTRAL) == L * (
        ATTN + 2 * DENSE_MLP + D * 8)


def test_prefill_chunk_flops():
    got = F.prefill_chunk_flops(MISTRAL, 512, 1024)
    keys = 512 * 1024 + 512 * 513 // 2
    want = (2 * 512 * L * (ATTN + DENSE_MLP)
            + L * 4 * H * HD * keys + 2 * D * V)
    assert got == want
    # Mixtral counts the routed two of eight experts, not the dense-all path
    ratio = F.prefill_chunk_flops(MIXTRAL, 512, 0) / F.prefill_chunk_flops(
        MISTRAL, 512, 0)
    assert 1.7 < ratio < 1.85


def test_peaks_listed_and_unlisted():
    assert peaks.peak_for("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError, match="peaks.py"):
        peaks.peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


@pytest.mark.parametrize("name,chips", [("mistral-7b-instruct-v0.2", 1),
                                        ("mixtral-8x7b-instruct-v0.1", 4)])
def test_config_file_keeps_published_widths(name, chips):
    cfg = _config(name)
    assert cfg["reduced"] == []
    for entry in MAN["configs"]:
        if entry["name"] == name:
            assert entry["reduced"] == [] and entry["source"] == cfg["source"]
            assert entry["file"] == f"benchmarks/configs/{name}.json"
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_key_value_heads"],
            cfg["vocab_size"], cfg["rope_theta"]) == (
        4096, 14336, 32, 8, 32000, 1e6)
    assert cfg["precision"]["weights"] == "int8"
    assert cfg["layout"]["chips"] == chips
    for w in MAN["workloads"]:
        if w["config"] == name:
            assert w["chips"] == chips
