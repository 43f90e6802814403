"""What the families with a stack that is not uniform share
(models/exaone_moe.py, models/lfm2_moe.py, models/deepseek_v3.py): the plan of the stack and the
walk over it, and the sigmoid-routed expert layer.

The expert layer (`moe`): scores in float32, the top k of score + bias
chosen, weights the chosen scores normalised over all k (plus the
configuration's `route_norm_eps`) and scaled. The layer is told which
experts it holds (`held_experts` = (first, count) of `n_experts`): it
routes over all of them, computes the part its own experts give, adds the
shared expert where the configuration has one (`n_shared_experts`), and
passes that sum on. With every expert held that is the whole layer; with a
share it is what one rank of expert parallelism computes before the
exchange, and no code here stands in for the other ranks. Dropless and
exact in both ways of multiplying (`experts_every`, `experts_grouped`);
which one a call takes is read off its static token count alone.

A configuration is anything with the attributes read here: `dtype`,
`n_experts_per_token`, `norm_topk_prob`, `route_norm_eps`,
`routed_scaling_factor`, `held_experts`, `n_shared_experts`; and, where
its router limits the choice to some groups of experts, `n_group` and
`topk_group` (models/deepseek_v3.py).
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from substratus_tpu.ops import scopes
from substratus_tpu.ops.basics import swiglu
from substratus_tpu.ops.quant import materialize, qeinsum

Params = Dict[str, Any]


# -- the stack's shape ---------------------------------------------------------

def layer_plan(kinds: Sequence[Any]) -> Tuple[int, int, int]:
    """(head, period, periods) for a stack whose layer l is of kind
    `kinds[l]`: the first `head` layers run one by one, the rest as
    `periods` repeats of `period` layers, scanned. Of all such splits the
    one that traces the fewest blocks; on a tie the most repeats, then the
    shortest head."""
    kinds = list(kinds)
    n = len(kinds)

    def cost(split):
        head, period, reps = split
        return (head + period, -reps, head)

    best = (n, 0, 0)
    for period in range(1, n + 1):
        for head in range(n - period, -1, -1):
            if head < n - period and kinds[head] != kinds[head + period]:
                break  # a longer run of this period only adds mismatches
            if (n - head) % period == 0:
                best = min(best, (head, period, (n - head) // period),
                           key=cost)
    return best


def take(tree, i):
    """Layer i of a stack of leaves (QTensor scales ride along)."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def run_stack(kinds: Sequence[Any], layer, carry):
    """Every layer of the stack in order, inside the region LAYERS:
    `layer_plan`'s head one by one, its periods under one `lax.scan`.
    `kinds[l]` is the tuple of kinds layer l is of (its operator's, its
    FFN's), each kind with a stack of its own (weights, cache) that holds
    the layers of that kind alone. `carry = layer(carry, j, l, at)`: j the
    layer whose kinds apply (static), l the layer's number (traced under
    the scan), `at(kind)` its index among the layers of one of its kinds
    (traced likewise)."""
    kinds = [tuple(pair) for pair in kinds]
    head, period, reps = layer_plan(kinds)
    seen: Dict[Any, int] = {}
    first = []  # for every layer, its index within each stack it reads
    for pair in kinds:
        first.append({kind: seen.get(kind, 0) for kind in pair})
        for kind in pair:
            seen[kind] = seen.get(kind, 0) + 1
    # how many layers of each kind one period adds to its stacks
    span = kinds[head:head + period]
    per = {kind: sum(kind in pair for pair in span) for kind in seen}

    def one(carry, j, i):
        """Layer j, i periods further along (i = 0: layer j itself)."""
        return layer(carry, j, j + i * period,
                     lambda kind: first[j][kind] + i * per[kind])

    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope(scopes.LAYERS):
        for j in range(head):
            carry = one(carry, j, zero)
        if reps:
            def body(carry, i):
                for j in range(head, head + period):
                    carry = one(carry, j, i)
                return carry, None

            carry, _ = lax.scan(body, carry,
                                jnp.arange(reps, dtype=jnp.int32))
    return carry


def heads_proj(h, w, heads: int, qe, dt):
    """h [B, S, D] -> [B, S, heads, hd] through w [heads, hd, D] (a layer
    of a stack `heads_first` viewed: its slice feeds the dot from the
    stack) or through the flat w [heads * hd, D]."""
    if len(w.shape) == 3:
        return qe("bsd,hkd->bshk", h, w, dt)
    out = qe("bsd,nd->bsn", h, w, dt)
    return out.reshape(out.shape[:2] + (heads, out.shape[-1] // heads))


def heads_first(w, heads: int):
    """A projection stack [L, heads * hd, D] viewed [L, heads, hd, D], a
    QTensor's scale with it (where it is 1 wide, [L, 1, 1, D]): no byte
    moves on a TPU while 128 divides hd, so a program does it where it
    takes the tree. Sliced by layer from the flat form, an int8 layer was
    written out anew before its dot read it; from this one the dot's own
    fusion slices it (PERF.md section 6, PR 41). A `Q4Tensor` is left as
    it is: its packed dim is the contracted one, and `heads_proj` and the
    family's output projection take the flat leaf too."""
    from substratus_tpu.ops.quant4 import Q4Tensor

    if isinstance(w, Q4Tensor):
        return w

    def view(a):
        n = a.shape[1]
        split = (1, 1) if n == 1 else (heads, n // heads)
        return a.reshape(a.shape[:1] + split + a.shape[2:])

    return jax.tree.map(view, w)


def projections_heads_first(layers: Params, n_heads: int,
                            n_kv_heads: int) -> Params:
    """`layers` with its four projection stacks (`wq`, `wk`, `wv` and the
    output's `wo`, each [L, heads * hd, D]) viewed by `heads_first`."""
    out = dict(layers)
    for name, heads in (("wq", n_heads), ("wk", n_kv_heads),
                        ("wv", n_kv_heads), ("wo", n_heads)):
        out[name] = heads_first(layers[name], heads)
    return out


def out_proj(o, w, dt):
    """o [B, S, heads, hd] -> [B, S, D] through w [heads, hd, D] (a layer
    of a stack `heads_first` viewed) or through the flat w [heads * hd,
    D]. Weight-only whatever the activations' kind: it contracts two
    dims."""
    if len(w.shape) == 3:
        return qeinsum("bshk,hkd->bsd", o, w, dt)
    return qeinsum("bsn,nd->bsd", o.reshape(o.shape[:2] + (-1,)), w, dt)


# -- the expert layer ----------------------------------------------------------

def route(h, router, bias, cfg):
    """h [T, D] -> (chosen experts [T, k] int32, their weights [T, k]
    float32). The bias moves the choice and never the weight; the weights
    are normalised over all k chosen, held here or not.

    A configuration with `n_group` > 1 limits the choice to `topk_group`
    of its groups of neighbouring experts (DeepSeek-V3's `noaux_tc`): a
    group scores the sum of its two largest score + bias, the best groups
    stay, and outside them score + bias is masked to zero (as published:
    to zero, not to minus infinity) before the k largest are taken."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h.astype(jnp.float32), materialize(router, jnp.float32)))
    c = s + bias.astype(jnp.float32)
    groups = getattr(cfg, "n_group", 1)
    if groups > 1:
        t, e = c.shape
        by_group = c.reshape(t, groups, e // groups)
        best_two, _ = lax.top_k(by_group, 2)
        _, kept = lax.top_k(jnp.sum(best_two, axis=-1), cfg.topk_group)
        stays = jnp.sum(jax.nn.one_hot(kept, groups, dtype=jnp.int32), axis=1)
        c = jnp.where(stays[..., None] > 0, by_group, 0.0).reshape(t, e)
    _, idx = lax.top_k(c, cfg.n_experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.route_norm_eps)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# How a call multiplies its held experts is chosen by its static token
# count alone (`moe`): up to EVERY_AT_MOST tokens, every token through
# every held expert; above it, token-expert pairs grouped by expert,
# BLOCK_ROWS rows of one expert at a time. On a v5e, a layer: 16 held of
# 128 experts of 6,144 x 2,048, 64 tokens 0.99 ms every / 1.19 ms grouped,
# 512 tokens 4.25 / 1.69 (chip run, PR 27); 64 held of 64 experts of
# 2,048 x 1,536: PERF.md section 6, PR 33.
EVERY_AT_MOST = 64
BLOCK_ROWS = 64


def gated(x, gate, up, down, eq_in, eq_out, qe, dt):
    return qe(eq_out, swiglu(qe(eq_in, x, gate, dt), qe(eq_in, x, up, dt)),
              down, dt)


def experts_every(h, local, w, mp, cfg, qe):
    """Every token through every held expert, mixed by the routing weights
    (zero where a token did not choose the expert)."""
    eh = cfg.held_experts[1]
    mix = jnp.sum(jax.nn.one_hot(local, eh, dtype=jnp.float32)
                  * w[..., None], axis=1)  # [T, Eh]; one_hot(-1) is zero
    out = gated(h, mp["w_gate"], mp["w_up"], mp["w_down"],
                "td,edm->tem", "tem,emd->ted", qe, cfg.dtype)
    return jnp.einsum("ted,te->td", out, mix.astype(cfg.dtype))


def experts_grouped(h, local, w, stack, layer, cfg, qe):
    """Token-expert pairs sorted by expert, each held expert multiplying
    its own rows `BLOCK_ROWS` at a time: the work follows the pairs that
    landed here, not tokens x held experts. A pair routed elsewhere sorts
    last and is never multiplied. An expert's weights are indexed out of
    the stack of all sparse layers inside the loop, by (layer, expert) at
    once: sliced by layer beforehand, the loop would be handed a copy of
    the layer's every expert."""
    t, k = local.shape
    eh, bm, dt = cfg.held_experts[1], BLOCK_ROWS, cfg.dtype
    n = t * k
    key = jnp.where(local >= 0, local, eh).reshape(n)
    order = jnp.argsort(key, stable=True)
    tok = (order // k).astype(jnp.int32)  # the token of each sorted pair
    counts = jnp.sum(jax.nn.one_hot(key, eh, dtype=jnp.int32), axis=0)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    blocks = -(-counts // bm)
    block_ends = jnp.cumsum(blocks)
    # block b belongs to expert e_b and starts at sorted row r0_b
    n_max = -(-n // bm) + eh
    b_ids = jnp.arange(n_max, dtype=jnp.int32)
    e_b = jnp.minimum(
        jnp.searchsorted(block_ends, b_ids, side="right"), eh - 1
    ).astype(jnp.int32)
    r0_b = starts[e_b] + (b_ids - (block_ends[e_b] - blocks[e_b])) * bm
    tok_pad = jnp.concatenate([tok, jnp.zeros((bm,), jnp.int32)])

    def one(b, out):
        rows = lax.dynamic_slice_in_dim(tok_pad, r0_b[b], bm)
        we = jax.tree.map(
            lambda a: lax.dynamic_slice(
                a, (layer, e_b[b]) + (0,) * (a.ndim - 2),
                (1, 1) + a.shape[2:]).reshape(a.shape[2:]),
            {name: stack[name] for name in EXPERT_LEAVES})
        y = gated(h[rows], we["w_gate"], we["w_up"], we["w_down"],
                  "td,dm->tm", "tm,md->td", qe, dt)
        # rows past this expert's end are the next expert's: its own block
        # overwrites them, and the last expert's spill lands past `ends`
        return lax.dynamic_update_slice_in_dim(out, y, r0_b[b], axis=0)

    out = lax.fori_loop(0, block_ends[-1], one,
                        jnp.zeros((n + bm, h.shape[-1]), dt))
    back = jnp.argsort(order)  # sorted row of pair (token, choice)
    y = out[back].reshape(t, k, -1)
    w = jnp.where(local >= 0, w, 0.0).astype(dt)  # hides the spill too
    return jnp.einsum("tkd,tk->td", y, w)


def moe(h, stack, layer, cfg, valid, qe):
    """The sparse layer's partial sum over the held experts, plus the
    shared expert where the configuration has one. h [B, S, D]; `stack`
    the leaves of every sparse layer, `layer` this one's index among them;
    returns (y [B, S, D], counters: `COUNTERS`)."""
    b, s, d = h.shape
    first, eh = cfg.held_experts
    flat = h.reshape(b * s, d)
    mp = take({k: v for k, v in stack.items() if k not in EXPERT_LEAVES},
              layer)
    with jax.named_scope(scopes.MOE_ROUTER):
        idx, w = route(flat, mp["router"], mp["router_bias"], cfg)
        here = (idx >= first) & (idx < first + eh)
        local = jnp.where(here, idx - first, -1)
        real = valid.reshape(b * s, 1)
        per_expert = jnp.sum(
            jax.nn.one_hot(jnp.where(real, local, -1), eh, dtype=jnp.int32),
            axis=(0, 1))
        stats = {
            "moe_pairs_held": jnp.sum(per_expert),
            "moe_pairs_all": jnp.sum(real) * cfg.n_experts_per_token,
            "moe_expert_pairs_max": jnp.max(per_expert),
            "moe_experts_touched": jnp.sum(per_expert > 0, dtype=jnp.int32),
        }
    with jax.named_scope(scopes.MOE_EXPERTS):
        if b * s > EVERY_AT_MOST:
            y = experts_grouped(flat, local, w, stack, layer, cfg, qe)
        else:
            held = take({k: stack[k] for k in EXPERT_LEAVES}, layer)
            y = experts_every(flat, local, w, held, cfg, qe)
    if cfg.n_shared_experts:
        with jax.named_scope(scopes.MOE_SHARED):
            y = y + gated(flat, mp["shared_gate"], mp["shared_up"],
                          mp["shared_down"], "td,dm->tm", "tm,md->td", qe,
                          cfg.dtype)
    return y.reshape(b, s, d), stats


# What `moe` counts, over the real tokens of a call: token-expert pairs
# that landed on a held expert, pairs in all, the most pairs one held
# expert received, and the held experts that received any. A family's
# forward carries those it names in its totals (`zero_counters`): what it
# leaves out is never computed.
COUNTERS = ("moe_pairs_held", "moe_pairs_all", "moe_expert_pairs_max",
            "moe_experts_touched")


def zero_counters(names: Sequence[str] = COUNTERS) -> Params:
    zero = jnp.zeros((), jnp.int32)
    return {name: zero for name in names}


def fold(total: Params, stats) -> Params:
    """`total` with a sparse layer's counters added (the `_max` one by
    maximum); unchanged for a layer that counts nothing."""
    if stats is None:
        return total
    return {name: (jnp.maximum if name.endswith("_max") else jnp.add)(
        value, stats[name]) for name, value in total.items()}
