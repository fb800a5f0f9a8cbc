"""Tensor-parallel sharding rules for the transformer parameter pytree.

Megatron-style TP expressed as GSPMD sharding annotations — no hand-written
collectives in the model: Q/K/V and MLP up/gate projections are
column-parallel (output features sharded over the 'tp' axis), attention
output and MLP down projections are row-parallel (input features sharded), so
XLA inserts exactly one all-reduce after attention and one after the MLP,
riding ICI.  The (tiny, 512-row byte-level) embedding and the norms are
replicated.

The same rules serve inference (engine on a tier submesh) and training
(mesh with ('dp','tp') axes — pass ``data_axis`` so batch dims shard over dp).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import ModelConfig


def param_specs(cfg: ModelConfig, tp_axis: str = "tp",
                ep_axis: str = "ep") -> Dict[str, Any]:
    """PartitionSpec pytree matching the model family's param structure
    (dense transformer or MoE — expert weights gain a leading [E] dim
    sharded over the 'ep' axis)."""
    t = tp_axis
    layers: Dict[str, P] = {
        "ln1": P(None, None),
        "wq": P(None, None, t),          # column parallel (heads)
        "wk": P(None, None, t),
        "wv": P(None, None, t),
        "wo": P(None, t, None),          # row parallel
        "ln2": P(None, None),
    }
    if cfg.num_experts > 1:
        layers.update({
            "w_router": P(None, None, None),
            "w_gate": P(None, ep_axis, None, t),   # [L, E, H, F]
            "w_up": P(None, ep_axis, None, t),
            "w_down": P(None, ep_axis, t, None),   # [L, E, F, H]
        })
    else:
        layers.update({
            "w_gate": P(None, None, t),  # column parallel (ffn)
            "w_up": P(None, None, t),
            "w_down": P(None, t, None),  # row parallel
        })
    return {
        "embed": P(None, None),
        "layers": layers,
        "final_ln": P(None),
    }


def param_shardings(cfg: ModelConfig, mesh: Mesh,
                    tp_axis: str = "tp") -> Dict[str, Any]:
    """NamedSharding pytree for placing params on a tier mesh.  Axes the
    mesh doesn't have (e.g. 'ep' on a tp-only serving mesh) or that don't
    divide their dimension fall back to replication, so MoE models serve
    on plain tensor-parallel tiers."""
    if cfg.num_heads % mesh.shape[tp_axis] or cfg.num_kv_heads % mesh.shape[tp_axis]:
        raise ValueError(
            f"tp={mesh.shape[tp_axis]} must divide heads "
            f"({cfg.num_heads}/{cfg.num_kv_heads}) for {cfg.name}")
    return _shardings_with_fallback(cfg, mesh, param_specs(cfg, tp_axis))


def train_param_specs(cfg: ModelConfig, dp_axis: str = "dp",
                      tp_axis: str = "tp",
                      ep_axis: str = "ep") -> Dict[str, Any]:
    """FSDP × TP (× EP) specs for training: on top of the Megatron TP
    rules, each weight's *other* matmul dimension is sharded over the data
    axis (ZeRO-3 style), so optimizer state and gradients scale down with
    dp; MoE expert weights additionally shard their [E] dim over 'ep'.
    GSPMD inserts the all-gathers before use and reduce-scatters on grads.
    Norm vectors stay replicated (tiny).
    """
    d, t, e = dp_axis, tp_axis, ep_axis
    layers: Dict[str, P] = {
        "ln1": P(None, None),
        "wq": P(None, d, t),
        "wk": P(None, d, t),
        "wv": P(None, d, t),
        "wo": P(None, t, d),
        "ln2": P(None, None),
    }
    if cfg.num_experts > 1:
        layers.update({
            "w_router": P(None, d, None),
            "w_gate": P(None, e, d, t),
            "w_up": P(None, e, d, t),
            "w_down": P(None, e, t, d),
        })
    else:
        layers.update({
            "w_gate": P(None, d, t),
            "w_up": P(None, d, t),
            "w_down": P(None, t, d),
        })
    return {
        "embed": P(d, None),
        "layers": layers,
        "final_ln": P(None),
    }


def train_param_shardings(cfg: ModelConfig, mesh: Mesh,
                          dp_axis: str = "dp",
                          tp_axis: str = "tp") -> Dict[str, Any]:
    """NamedSharding pytree for FSDP×TP training placement.  Axes that are
    absent from the mesh, or that do not divide the dimension they shard
    (tiny test models on wide meshes), fall back to replication — so the
    same rules serve any mesh from ('dp','sp','tp') down to a single-axis
    or single-device mesh."""
    return _shardings_with_fallback(cfg, mesh,
                                    train_param_specs(cfg, dp_axis, tp_axis))


def quantized_param_specs(cfg: ModelConfig, tp_axis: str = "tp",
                          ep_axis: str = "ep") -> Dict[str, Any]:
    """PartitionSpec pytree matching ops.quant.quantize_params' output:
    each quantized leaf becomes {"q": <weight spec>, "s": <weight spec
    with the contraction axis unsharded — the scale is size 1 there>};
    norms and the MoE router keep their serving specs."""
    from ..ops.quant import _QUANT_LAYER_KEYS
    specs = param_specs(cfg, tp_axis, ep_axis)

    def qpair(spec: P, contract_axis: int) -> Dict[str, P]:
        s_spec = list(spec)
        s_spec[contract_axis] = None
        return {"q": spec, "s": P(*s_spec)}

    layers = dict(specs["layers"])
    for k in _QUANT_LAYER_KEYS:
        if k in layers:
            layers[k] = qpair(layers[k], -2)
    out = dict(specs)
    out["layers"] = layers
    out["embed"] = qpair(specs["embed"], -1)   # per-ROW scales [V, 1]
    return out


def quantized_param_shardings(cfg: ModelConfig, mesh: Mesh,
                              tp_axis: str = "tp",
                              shapes: Any = None) -> Dict[str, Any]:
    """NamedSharding pytree for an int8-quantized params tree on a tier
    mesh — int8 weight-only serving composes with tensor parallelism, so
    a tp submesh streams HALF the weight bytes per chip per decode step
    (decode is weight-bandwidth-bound; this is the whole point of int8).
    ``shapes``: pass an existing eval_shape of the quantized tree to skip
    re-tracing the init+quantize graph (hbm_budget already holds one)."""
    if cfg.num_heads % mesh.shape[tp_axis] or cfg.num_kv_heads % mesh.shape[tp_axis]:
        raise ValueError(
            f"tp={mesh.shape[tp_axis]} must divide heads "
            f"({cfg.num_heads}/{cfg.num_kv_heads}) for {cfg.name}")
    if shapes is None:
        from ..models import init_params
        from ..ops.quant import quantize_params
        shapes = jax.eval_shape(lambda: quantize_params(init_params(cfg, 0)))
    return _shardings_with_fallback(cfg, mesh, quantized_param_specs(
        cfg, tp_axis), shapes=shapes)


def _shardings_with_fallback(cfg: ModelConfig, mesh: Mesh,
                             specs: Dict[str, Any],
                             shapes: Any = None) -> Dict[str, Any]:
    """Map specs onto the mesh, dropping axes the mesh lacks or that don't
    divide the dimension they shard (tiny test models on wide meshes)."""
    from ..models import init_params
    if shapes is None:
        shapes = jax.eval_shape(lambda: init_params(cfg, seed=0))

    def fix(spec: P, shaped) -> NamedSharding:
        dims = shaped.shape
        fixed = []
        used = set()
        for i, ax in enumerate(spec):
            if (ax is None or ax in used or ax not in mesh.shape
                    or dims[i] % mesh.shape[ax]):
                fixed.append(None)
            else:
                fixed.append(ax)
                used.add(ax)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree.map(fix, specs, shapes,
                        is_leaf=lambda x: isinstance(x, P))


def kv_cache_specs(tp_axis: str = "tp",
                   quantized: bool = False,
                   sp_axis: str = None) -> Dict[str, P]:
    """KV cache [L, B, S, N_kv, D]: shard the kv-head axis over tp.  int8
    caches carry {ks,vs: [L, B, S, N_kv]} scale planes, same sharding.
    ``sp_axis``: additionally shard the SEQUENCE axis — sequence-parallel
    decode (parallel/sp_attention.py) keeps only S/sp cached positions
    per chip, so a tier's context capacity scales with its sp degree."""
    spec = {"k": P(None, None, sp_axis, tp_axis, None),
            "v": P(None, None, sp_axis, tp_axis, None)}
    if quantized:
        spec["ks"] = P(None, None, sp_axis, tp_axis)
        spec["vs"] = P(None, None, sp_axis, tp_axis)
    return spec


def kv_pool_specs(tp_axis: str = "tp",
                  quantized: bool = False) -> Dict[str, P]:
    """Paged KV pool [L, NB, bs, N_kv * D] (engine/paged_kv.py: token-major,
    the kv heads merged into the last axis, each a contiguous run of it):
    shard that axis over tp, like the contiguous cache's head axis — each
    shard owns its heads' columns of every block, and the decode step's
    scatter/gather stay shard-local.  int8 pools carry per-row scale
    planes [L, NB, bs, N_kv], head-sharded the same way."""
    spec = {"k": P(None, None, None, tp_axis),
            "v": P(None, None, None, tp_axis)}
    if quantized:
        spec["ks"] = P(None, None, None, tp_axis)
        spec["vs"] = P(None, None, None, tp_axis)
    return spec


def kv_pool_shardings(mesh: Mesh, tp_axis: str = "tp",
                      quantized: bool = False) -> Dict[str, NamedSharding]:
    return {k: NamedSharding(mesh, s)
            for k, s in kv_pool_specs(tp_axis, quantized).items()}


def kv_cache_shardings(mesh: Mesh, tp_axis: str = "tp",
                       quantized: bool = False,
                       sp_axis: str = None) -> Dict[str, NamedSharding]:
    return {k: NamedSharding(mesh, s)
            for k, s in kv_cache_specs(tp_axis, quantized,
                                       sp_axis=sp_axis).items()}


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
