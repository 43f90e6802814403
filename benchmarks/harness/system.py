"""The one place where the harness touches the system under test.

Everything the benchmark takes from the program goes through here: the
engine with its scheduler, paged cache, model step and kernels
(`substratus_tpu.serve.engine.Engine`, driven through `submit`/`start` as
`serve.main` drives it), its `Request`, its metrics registry, its
compile-cache helper, its model registry and its sharding rules. Traffic,
clocks, weights, counts, trace reduction and the reference are the
harness's own. Which of the program's model families a configuration runs
on, with which config, and what its weight tree looks like, the
configuration's family file says (`benchmarks/families/<family>.py`:
`program`, `leaf_table`); nothing here knows a block.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from benchmarks.harness import weights as W


def startup() -> Dict[str, Any]:
    """Compile cache (JAX_COMPILATION_CACHE_DIR, or the fixed in-checkout
    directory on an accelerator), compilation counters, the device line."""
    from substratus_tpu.utils.jaxstart import jax_startup

    return jax_startup()


def model_config(family, cfg: Dict[str, Any]):
    """The program's model configuration: the class its registry has under
    the name the family gives, built from the family's keyword arguments."""
    from substratus_tpu.models import registry

    name, kwargs = family.program(cfg)
    return registry.config_class(name)(**kwargs)


def build_mesh(cfg: Dict[str, Any], chips: int):
    """serve.main's choice for one host: tensor-parallel over the chips
    (the layout the configuration file states). None on one chip."""
    if chips == 1:
        return None
    import jax
    from substratus_tpu.parallel.mesh import build_mesh as _build

    tensor = int(cfg["layout"]["mesh"]["tensor"])
    devices = jax.devices()[:chips]
    return _build(data=chips // tensor, tensor=tensor, devices=devices)


def _wrap(tree):
    """Plain {"q","scale"} leaves -> the program's QTensor (no copy)."""
    from substratus_tpu.ops.quant import QTensor

    if isinstance(tree, dict):
        if set(tree) == {"q", "scale"}:
            return QTensor(q=tree["q"], scale=tree["scale"])
        return {k: _wrap(v) for k, v in tree.items()}
    return tree


def _unwrap(tree):
    from substratus_tpu.ops.quant import QTensor

    if isinstance(tree, QTensor):
        return {"q": tree.q, "scale": tree.scale}
    if isinstance(tree, dict):
        return {k: _unwrap(v) for k, v in tree.items()}
    return tree


def weight_shardings(family, cfg: Dict[str, Any], mesh):
    """Where the program would put each leaf (its serving rules), as a
    tree shaped like the harness's own."""
    if mesh is None:
        return None
    from substratus_tpu.models import registry
    from substratus_tpu.parallel.sharding import serve_rules_for, sharding_tree

    module = registry.module_for(family.program(cfg)[0])
    sh = sharding_tree(
        _wrap(W.tree_shapes(family.leaf_table(cfg))), mesh,
        module.param_logical_axes(model_config(family, cfg)),
        serve_rules_for(mesh),
    )
    return _unwrap(sh)


CONTROLS = ("int4", "w8a8", "int8kv")


def lower_weights(params, table: Dict[str, W.Leaf]):
    """The program's own int4 weights (ops/quant4.py: nibble-packed, one
    scale per group of 128) from the harness's int8 tree, leaf by leaf of
    the family's table. Each int8 leaf is deleted as soon as its int4 copy
    is made, so a 7B tree never holds both."""
    import jax
    import jax.numpy as jnp
    from substratus_tpu.ops.quant4 import quantize4

    def one(entry: W.Leaf, leaf):
        if entry.kind != "int8":
            return leaf
        contracting = entry.contracting
        if entry.stacked:  # the table counts the leading layer dim
            contracting = tuple(c - 1 for c in contracting)

        def layer(q, scale):
            return quantize4(q.astype(jnp.float32) * scale, contracting)

        def layers(q, scale):  # float32 of one layer at a time
            return jax.lax.map(lambda a: layer(*a), (q, scale))

        fn = jax.jit(layers if entry.stacked else layer)
        out = jax.block_until_ready(fn(leaf["q"], leaf["scale"]))
        leaf["q"].delete()
        leaf["scale"].delete()
        return out

    return W.nest({path: one(entry, W.at(params, path))
                   for path, entry in table.items()})


def build_engine(family, cfg: Dict[str, Any], engine_sizes: Dict[str, Any],
                 params, mesh, control: Optional[str] = None):
    """The engine as serve.main builds it for this configuration: paged
    layout (auto), overlap auto, no speculation, prefix cache on.

    `control` (never in a benchmark run) switches on one of the program's
    own lower-precision paths, the step below what the configuration
    states: "int4" weights (`params` from lower_weights), "w8a8" int8
    activations, "int8kv" an int8 KV cache."""
    from substratus_tpu.serve.engine import Engine, EngineConfig

    if control is not None and control not in CONTROLS:
        raise ValueError(f"control {control!r} not one of {CONTROLS}")
    ec = EngineConfig(
        max_batch=int(engine_sizes["max_batch"]),
        max_seq_len=int(engine_sizes["max_seq_len"]),
        max_prefill_len=int(engine_sizes["max_prefill_len"]),
        kv_pool_tokens=engine_sizes.get("kv_pool_tokens"),
        kv_cache_dtype="int8" if control == "int8kv" else "model",
        max_queue=None,
    )
    mcfg = model_config(family, cfg)
    if control == "w8a8":
        mcfg = mcfg.replace(quant_activations=True)
    return Engine(mcfg, _wrap(params), ec, mesh=mesh)


def precision_found(engine, table: Dict[str, W.Leaf]) -> Dict[str, str]:
    """The types the engine really holds, under the keys of the
    configuration's `precision`: of its matmul weights (the leaves the
    family's table makes int8), of what its matmuls take as activations,
    and of its KV cache's pages."""
    import jax.numpy as jnp
    from substratus_tpu.ops.quant import QTensor
    from substratus_tpu.ops.quant4 import Q4Tensor

    kinds = set()
    for path, entry in table.items():
        if entry.kind != "int8":
            continue
        leaf = W.at(engine.params, path)
        if isinstance(leaf, Q4Tensor):
            kinds.add("int4")
        else:
            kinds.add(str(leaf.q.dtype if isinstance(leaf, QTensor)
                          else leaf.dtype))
    return {
        "weights": "+".join(sorted(kinds)),
        "activations": "int8" if engine.cfg.quant_activations
        else str(jnp.dtype(engine.cfg.dtype)),
        "kv_cache": "+".join(sorted(
            {str(engine.cache[n].dtype) for n in ("k", "v")})),
    }


def new_request(prompt_tokens: List[int], max_tokens: int, sink, rid: str):
    """Greedy, exactly max_tokens long: eos_token_id -1 never matches, so
    the work is the same in every seed."""
    from substratus_tpu.serve.engine import Request

    return Request(
        prompt_tokens=prompt_tokens, max_tokens=max_tokens, temperature=0.0,
        eos_token_id=-1, out=sink, id=rid,
    )


def queue_wait_s(req) -> Optional[float]:
    """Submit -> first prefill, from the request's own journey (the same
    clock reading the program observes into
    substratus_serve_queue_wait_seconds, without the histogram's buckets)."""
    j = getattr(req, "journey", None)
    ev = j.marks.get("admit") if j is not None else None
    if not ev or not ev[2]:
        return None
    return ev[2].get("wait_us", 0) / 1e6


class Counters:
    """Deltas of the program's counters and histograms over a window."""

    HISTS = (
        "substratus_serve_batch_occupancy_ratio",
        "substratus_serve_queue_wait_seconds",
    )
    COUNTS = ("substratus_jax_compilations_total",)

    def __init__(self, engine):
        self.engine = engine

    def snapshot(self) -> Dict[str, Any]:
        from substratus_tpu.observability.metrics import METRICS

        snap: Dict[str, Any] = {"t": time.perf_counter()}
        for name in self.HISTS:
            s = METRICS.histogram_series(name).get("", {})
            snap[name] = (s.get("sum", 0.0), s.get("count", 0))
        for name in self.COUNTS:
            snap[name] = METRICS.get(name) or 0.0
        snap["stats"] = dict(self.engine.stats)
        return snap

    @staticmethod
    def delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for name in Counters.HISTS:
            ds, dc = b[name][0] - a[name][0], b[name][1] - a[name][1]
            out[name] = {"sum": ds, "count": dc,
                         "mean": ds / dc if dc else None}
        for name in Counters.COUNTS:
            out[name] = b[name] - a[name]
        out["stats"] = {
            k: b["stats"].get(k, 0) - a["stats"].get(k, 0) for k in b["stats"]
        }
        return out
