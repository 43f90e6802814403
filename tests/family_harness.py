"""What the family test files (test_exaone_moe, test_lfm2_moe, test_brumby,
test_deepseek_v3, test_glm_moe_dsa) share: the page table, the chunked
prefill and the one-live-slot decode step as serve/engine.py cuts them, over
a family module `M`, and the engine driven through `submit`.

Every step goes through one `jax.jit` of `M.forward` with the config static,
so a test's chunks and steps reuse one executable a shape: called eagerly,
each step dispatches the whole stack op by op and traces its scans again.
"""
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from substratus_tpu.ops.quant import QTensor, quantize_params
from substratus_tpu.serve.engine import Engine, EngineConfig, Request


def plain(tree):
    """The program's tree as the benchmark's harness spells it:
    QTensor -> {"q", "scale"}."""
    if isinstance(tree, QTensor):
        return {"q": tree.q, "scale": tree.scale}
    if isinstance(tree, dict):
        return {k: plain(v) for k, v in tree.items()}
    return tree


def table(slots, max_pages=16):
    """Slot s owns pages 1 + s * max_pages ..: page 0 is the trash page."""
    return (1 + np.arange(slots * max_pages, dtype=np.int32)
            .reshape(slots, max_pages))


def submit_all(eng, prompts, max_tokens):
    """Greedy requests, all in flight at once: each one's tokens."""
    reqs = [eng.submit(Request(prompt_tokens=[int(t) for t in p],
                               max_tokens=max_tokens, temperature=0.0,
                               eos_token_id=-1)) for p in prompts]
    outs = []
    for r in reqs:
        ids = []
        while (t := r.out.get(timeout=300)) is not None:
            ids.append(t)
        outs.append(ids)
    return outs


@functools.cache
def seeded_params(M, cfg, seed=0):
    """The family's tree from a key, int8 where the benchmark has int8, as
    one program (leaf by leaf, eagerly, a tiny tree takes 15-20 s)."""
    return jax.jit(lambda key: quantize_params(
        M.init_params(cfg, key), M.quant_contracting(cfg)))(
            jax.random.key(seed))


@functools.cache
def _step(M):
    """`M.forward` and, where the family counts on the device, the step's
    counters taken out of the cache it returns (as the engine's programs
    do), compiled once a config and shape."""
    counters = getattr(M, "step_counters", None)

    def step(params, tokens, cfg, **kw):
        logits, cache = M.forward(params, tokens, cfg, **kw)
        stats = counters(cache) if counters and kw.get("cache") else None
        return logits, cache, stats

    return jax.jit(step, static_argnums=(2,))


class Family:
    """The helpers bound to one family module, its tiny config, the chunk
    and the page its tests run at."""

    def __init__(self, M, cfg, chunk=16, page=4, slots=3):
        self.M, self.cfg = M, cfg
        self.chunk, self.page, self.slots = chunk, page, slots
        # deepseek_v3 keeps pages alone: its forward takes no `slots`
        self._takes_slots = "slots" in inspect.signature(M.forward).parameters

    def forward(self, params, tokens, cfg, **kw):
        """`M.forward`, compiled: (logits, cache)."""
        return _step(self.M)(params, tokens, cfg, **kw)[:2]

    def _table(self, bt, rows):
        # Brumby reads no page: its tests pass no table, and the program
        # is handed one of zeros, as the engine hands every family one.
        return np.zeros((rows, 4), np.int32) if bt is None else bt

    def prefill(self, params, cfg, cache, toks, slot, bt=None, chunk=None,
                start=0):
        """Chunks as serve/engine.py::_chunk_prefill_jit cuts them
        (right-padded to the chunk, padded positions clamped one past the
        prompt), through the model's own forward: every real row's
        logits."""
        chunk = chunk or self.chunk
        bt = self._table(bt, slot + 1)
        kw = {"slots": jnp.asarray([slot])} if self._takes_slots else {}
        rows = []
        for off in range(start, len(toks), chunk):
            part = toks[off:off + chunk]
            n = len(part)
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :n] = part
            pos = np.minimum(off + np.arange(chunk), off + n)[None]
            logits, cache, _ = _step(self.M)(
                params, jnp.asarray(padded), cfg, positions=jnp.asarray(pos),
                cache=cache, block_table=jnp.asarray(bt[slot:slot + 1]),
                valid=jnp.arange(chunk)[None] < n, **kw)
            rows.append(np.asarray(logits[0, :n]))
        return np.concatenate(rows), cache

    def decode(self, params, cfg, cache, tok, pos, slot, bt=None):
        """One decode step of a batch in which only `slot` is live: its
        logits, the cache, and the step's counters (None for a family
        that counts nothing on the device)."""
        bt = self._table(bt, self.slots)
        b = bt.shape[0]
        toks = np.zeros((b,), np.int32)
        toks[slot] = tok
        posv = np.zeros((b,), np.int32)
        posv[slot] = pos
        live = np.arange(b) == slot
        logits, cache, stats = _step(self.M)(
            params, jnp.asarray(toks)[:, None], cfg,
            positions=jnp.asarray(posv)[:, None], cache=cache,
            block_table=jnp.asarray(np.where(live[:, None], bt, 0)),
            valid=jnp.asarray(live)[:, None])
        return np.asarray(logits[slot, 0]), cache, stats

    def serve(self, params, prompts, max_tokens, cfg=None, **ec):
        """An engine of the family started, the prompts served greedily
        all at once, the engine stopped: (tokens a prompt, the engine)."""
        ec = {"max_batch": self.slots, "max_seq_len": 96,
              "max_prefill_len": self.chunk, "page_size": self.page, **ec}
        eng = Engine(cfg or self.cfg, params, EngineConfig(**ec),
                     model=self.M)
        eng.start()
        outs = submit_all(eng, prompts, max_tokens)
        eng.stop()
        assert eng.error is None
        return outs, eng
