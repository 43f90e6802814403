"""The region vocabulary (ops/scopes.py) in the engine's compiled programs:
every name that applies is on some op's `op_name`, every dot, gather and
scatter sits under one, and the jitted functions keep the names the
benchmark's readers find them by. CPU only: a scope is HLO metadata."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import (
    deepseek_v3, exaone_moe, granitemoehybrid, lfm2_moe, llama,
)
from substratus_tpu.ops import scopes
from substratus_tpu.serve.engine import Engine, EngineConfig

DENSE_LLAMA = {scopes.EMBED, scopes.LAYERS, scopes.NORM, scopes.ATTN_QKV,
               scopes.KV_WRITE, scopes.ATTN_CORE, scopes.ATTN_OUT, scopes.MLP,
               scopes.LM_HEAD}
CASES = {
    # name: (model config, kv layout, the names that apply in both programs)
    "llama-paged": ("tiny", "paged", DENSE_LLAMA | {scopes.KV_GATHER}),
    "llama-dense-cache": ("tiny", "dense", DENSE_LLAMA),
    "moe-paged": ("tiny-moe", "paged",
                  (DENSE_LLAMA - {scopes.MLP})
                  | {scopes.KV_GATHER, scopes.MOE_ROUTER, scopes.MOE_EXPERTS}),
    "moe-dense-cache": ("tiny-moe", "dense",
                        (DENSE_LLAMA - {scopes.MLP})
                        | {scopes.MOE_ROUTER, scopes.MOE_EXPERTS}),
    # window layers beside global ones, a dense layer before sparse ones:
    # every base name but none is missing, and the family's three
    "exaone-moe-paged": ("tiny-exaone-moe", "paged",
                         DENSE_LLAMA | {scopes.KV_GATHER, scopes.MOE_ROUTER,
                                        scopes.MOE_EXPERTS}
                         | set(scopes.EXTRA)),
    # convolution layers beside attention layers, two dense layers before
    # sparse ones: every base name, and the family's three
    "lfm2-moe-paged": ("tiny-lfm2-moe", "paged",
                       DENSE_LLAMA | {scopes.KV_GATHER, scopes.MOE_ROUTER,
                                      scopes.MOE_EXPERTS}
                       | set(scopes.CONV)),
    # latent attention in every layer, a dense layer before sparse ones:
    # every base name and the shared expert's; the absorbed form's name in
    # the step alone and the expanded form's in the chunk alone
    # (`LATENT_ONLY` below)
    "deepseek-v3-paged": ("tiny-deepseek-v3", "paged",
                          DENSE_LLAMA | {scopes.KV_GATHER, scopes.MOE_ROUTER,
                                         scopes.MOE_EXPERTS,
                                         scopes.MOE_SHARED}),
    # the same block under a learned index: its two regions in both
    # programs; the step gathers no context (the keys are scored under
    # `attn.index`, the picked rows read under `attn.core`), the chunk's
    # XLA form still does
    "glm-dsa-paged": ("tiny-glm-dsa", "paged",
                      DENSE_LLAMA | {scopes.MOE_ROUTER, scopes.MOE_EXPERTS,
                                     scopes.MOE_SHARED, *scopes.INDEXED}),
    # Mamba-2 mixers beside attention layers without positions: every base
    # name, the held `conv.state` and the mixer's three; the in-block
    # scores in the chunk alone (`LATENT_ONLY` below)
    "granite-hybrid-paged": ("tiny-granite-hybrid", "paged",
                             DENSE_LLAMA | {scopes.KV_GATHER,
                                            scopes.CONV_STATE, scopes.SSM_IN,
                                            scopes.SSM_STATE,
                                            scopes.SSM_OUT}),
}
# What one of the two programs of a family opens and the other does not.
LATENT_ONLY = {"deepseek-v3-paged": {"decode": {scopes.ATTN_ABSORB},
                                     "chunk": {scopes.ATTN_EXPAND}},
               "granite-hybrid-paged": {"chunk": {scopes.SSM_INTRA}},
               "glm-dsa-paged": {"decode": {scopes.ATTN_ABSORB},
                                 "chunk": {scopes.ATTN_EXPAND,
                                           scopes.KV_GATHER}}}
OP_RE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?op_name=\"([^\"]*)\"", re.M)


def _engine(config: str, layout: str) -> Engine:
    model = next((m for m in (exaone_moe, lfm2_moe, deepseek_v3,
                              granitemoehybrid)
                  if config in m.CONFIGS), llama)
    cfg = model.CONFIGS[config].replace(dtype=jnp.float32)
    # one program: eagerly an expert family's tree is built leaf by leaf
    params = jax.jit(lambda key: model.init_params(cfg, key))(
        jax.random.key(0))
    return Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=64, max_prefill_len=16, kv_layout=layout))


def _compiled(e: Engine):
    """The engine's decode and chunk-prefill programs as the compiler
    leaves them, from the engine's own jits and arguments."""
    bt = e.block_table if e.paged else None
    decode = e._decode_fn.lower(
        e.params, e.cache, bt, e.tokens, e.positions, e.temps, e.top_ps,
        e.key, None, None, *((e.active,) if e._tells_valid else ())
    ).compile().as_text()
    if e.paged:
        cache, row = e.cache, e.block_table[:1]
    else:
        cache, row = e._extract_slot(e.cache, 0), None
    chunk = Engine._chunk_prefill_jit.lower(
        e.model, e.cfg, e.params, cache, np.zeros((1, 16), np.int32), 0, 16,
        block_table=row, **({"slot": np.int32(0)} if e._tells_valid else {})
    ).compile().as_text()
    return decode, chunk


def _scope_of(op_name: str):
    for part in reversed(op_name.split("/")):
        if part in scopes.EVERY:
            return part
    return None


@pytest.mark.parametrize("case", sorted(CASES))
def test_regions_in_the_compiled_decode_and_chunk_programs(case):
    config, layout, want = CASES[case]
    e = _engine(config, layout)
    assert e.paged == (layout == "paged")
    decode, chunk = _compiled(e)
    # the names the benchmark's readers find the programs by
    assert re.search(r"^HloModule jit_decode\b", decode, re.M)
    assert re.search(r"^HloModule jit__chunk_prefill_jit\b", chunk, re.M)
    for program, text, extra in (("decode", decode, {scopes.SAMPLE}),
                                 ("chunk", chunk, set())):
        ops = OP_RE.findall(text)
        assert ops, program
        found = {_scope_of(name) for _, name in ops} - {None}
        extra = extra | LATENT_ONLY.get(case, {}).get(program, set())
        assert found == want | extra, (program, found ^ (want | extra))
        # the heavy ops each sit under a region
        for opcode, name in ops:
            if opcode in ("dot", "gather", "scatter", "convolution"):
                assert _scope_of(name) is not None, (program, opcode, name)
        # scopes nest only as the table says: block regions inside
        # `layers`, the others beside it, never one block region in another
        for _, name in ops:
            parts = [p for p in name.split("/") if p in scopes.EVERY]
            if not parts:
                continue
            inner = parts[-1]
            if inner in (scopes.EMBED, scopes.LM_HEAD, scopes.SAMPLE,
                         scopes.LAYERS):
                assert parts == [inner], name
            elif name.startswith("jit("):  # reducers carry the bare scope
                # (a chunk's in-block part sits in the region of its scan)
                assert parts in ([scopes.LAYERS, inner],
                                 [scopes.LAYERS, scopes.SSM_STATE,
                                  scopes.SSM_INTRA]), name


@pytest.mark.parametrize("case", ["llama-paged", "lfm2-moe-paged"])
def test_both_branches_of_the_sampler_lie_under_sample(case):
    """ops/sampling.py::sample branches on its temperatures: the
    conditional, the sort and the draw of its sampled branch and the argmax
    beside it are all charged to `sample`, and nothing of it is unscoped."""
    config, layout, _ = CASES[case]
    decode, _ = _compiled(_engine(config, layout))
    ops = OP_RE.findall(decode)
    assert {op for op, _ in ops} >= {"conditional", "sort"}
    for op, name in ops:
        # (a scan's `while/cond/` is the loop's test, not a branch)
        if "/cond/branch_" in name or op in ("conditional", "sort"):
            assert _scope_of(name) == scopes.SAMPLE, (op, name)
    # the sort is in the sampled branch alone
    assert all("/cond/branch_1_fun/" in name for op, name in ops if op == "sort")


def test_vocabulary_is_closed_and_matches_the_benchmarks_copy():
    from benchmarks.harness import trace_scopes

    assert tuple(trace_scopes.SCOPES) == tuple(scopes.ALL)
    assert len(set(scopes.ALL)) == len(scopes.ALL) == 13
    # what one family's block adds is listed in that family's file, and
    # the benchmark's readers charge an op to any of them
    assert len(set(scopes.EVERY)) == len(scopes.EVERY) == 29
    assert scopes.INDEXED == ("attn.index", "attn.select")
    assert scopes.SSM == ("ssm.in", "ssm.state", "ssm.intra", "ssm.out")
    assert set(scopes.EXTRA + scopes.CONV + scopes.RET + scopes.LATENT
               + scopes.INDEXED + scopes.SSM) <= trace_scopes.vocabulary()


def test_a_region_name_is_metadata_and_changes_no_arithmetic(monkeypatch):
    """The program as lowered is the same text with the scopes and with
    jax.named_scope made a no-op: naming regions costs nothing when no
    capture runs, which is why there is no switch."""
    import contextlib

    def lowered():
        e = _engine("tiny", "paged")
        return e._decode_fn.lower(
            e.params, e.cache, e.block_table, e.tokens, e.positions, e.temps,
            e.top_ps, e.key, None, None)

    named = lowered()
    assert "kv.gather" in named.compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lowered()
    assert "kv.gather" not in bare.compile().as_text()
    assert named.as_text() == bare.as_text()


def test_the_compile_cache_key_holds_the_names(monkeypatch):
    """...and because the arithmetic is the same, a cache keyed without
    metadata would hand a build with names an older build's executable,
    and a capture would show the older names (seen on the chip, PR 24)."""
    from substratus_tpu.utils import jaxstart

    monkeypatch.delenv(jaxstart.CACHE_ENV, raising=False)
    jaxstart.configure_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True
