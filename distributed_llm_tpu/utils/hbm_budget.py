"""Serving HBM budget: does a tier's model + KV actually fit its submesh?

The flagship presets (nano_1b / orin_8b / moe_8x1b) were once
"dead config": nothing verified that orin_8b (~7B params, ~14 GB
bf16) plus a KV pool fits its tp=4 submesh at 16 GB/chip.  This module
budgets a tier with ``jax.eval_shape`` over the REAL code paths — the
model family's init (models/__init__.py), the serving quantizer
(ops/quant.quantize_params), the contiguous cache / paged pool
allocators, and the tensor-parallel sharding rules
(parallel/sharding.py) — so no weights materialize and the 8B-class
budget runs on the CPU test box.

The reference never had this problem (Ollama picks GGML files sized for
the Jetson); a framework that owns its engine has to prove residency.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

# The bench chip (TPU v5e) — overridable per deployment.
DEFAULT_HBM_PER_CHIP_GB = 16.0


def _tree_gb(tree: Any) -> float:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree)) / 1e9


def _sharded_tree_gb(tree: Any, shardings: Any) -> float:
    """Per-chip bytes under NamedShardings (max over chips is what HBM
    residency cares about; these rules shard evenly)."""
    total = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(shardings)):
        shard = sh.shard_shape(leaf.shape)
        total += int(np.prod(shard)) * leaf.dtype.itemsize
    return total / 1e9


def tier_hbm_budget(tier, devices: Optional[Sequence[jax.Device]] = None,
                    hbm_per_chip_gb: float = DEFAULT_HBM_PER_CHIP_GB,
                    mesh: Optional[jax.sharding.Mesh] = None
                    ) -> Dict[str, Any]:
    """Budget ``tier`` against its submesh.

    Returns {params_gb_per_chip, kv_gb_per_chip, total_gb_per_chip,
    chips, hbm_per_chip_gb, fits, headroom_gb}.  ``devices`` backs the
    tp>1 sharding evaluation (any devices do — CPU works); tp=1 tiers
    need none.

    With ``mesh`` (the tier's DEPLOYED submesh, e.g. from
    ``carve_tier_meshes``) the budget reads tp/sp/ep from the mesh axes
    instead of re-deriving them from the tier against the full local
    device count — a cluster's later tiers see only the chips earlier
    tiers left over, so the standalone derivation can certify a larger
    (smaller-footprint) sharding than any deployment uses.  Use
    ``cluster_hbm_budget`` to budget a whole cluster that way.
    """
    from .. import models
    from ..ops.quant import quantize_params

    cfg = tier.model()
    if mesh is not None:
        tp = mesh.shape.get("tp", 1)
        ep = mesh.shape.get("ep", 1)
        sp = mesh.shape.get("sp", 1)
        devices = list(mesh.devices.flat)
    else:
        tp = tier.tp
        sp = tier.sp
        # Budget the degree carve_tier_meshes would actually DEPLOY: ep
        # must divide the expert count and fit the devices (param_specs
        # silently replicates a non-dividing axis, which would certify a
        # sharding no deployment uses).
        from ..parallel.mesh import _fit_ep
        n_avail = (len(devices) if devices is not None
                   else len(jax.devices()))
        ep = _fit_ep(tier, n_avail, tp)
    chips = tp * max(1, sp, ep)

    # -- params (the serving engines' exact init + quantize pipeline) -----
    quantized = tier.quantize == "int8"
    if quantized:
        shapes = jax.eval_shape(
            lambda: quantize_params(models.init_params(cfg, 0)))
    else:
        shapes = jax.eval_shape(lambda: models.init_params(cfg, 0))
    if tp > 1 or ep > 1:
        from ..parallel.sharding import (param_shardings,
                                         quantized_param_shardings)
        if mesh is None:
            need = tp * ep
            if devices is None or len(devices) < need:
                devices = jax.devices()
            if len(devices) < need:
                raise ValueError(f"need {need} devices to evaluate the "
                                 f"tp×ep sharding, have {len(devices)}")
            from ..parallel.mesh import ep_tp_mesh, tp_mesh
            mesh = (ep_tp_mesh(list(devices)[:need], ep, tp) if ep > 1
                    else tp_mesh(list(devices)[:tp], tp))
        shardings = (quantized_param_shardings(cfg, mesh, shapes=shapes)
                     if quantized else param_shardings(cfg, mesh))
        params_gb = _sharded_tree_gb(shapes, shardings)
    else:
        params_gb = _tree_gb(shapes)

    # -- KV (the engine the tier would actually build) ---------------------
    if tier.decode_batch > 1:
        from ..engine.paged_kv import PagedConfig, init_pool
        pcfg = PagedConfig(block_size=tier.kv_block_size,
                           max_slots=tier.decode_batch,
                           max_seq_len=cfg.max_seq_len)
        pool = jax.eval_shape(lambda: init_pool(cfg, pcfg,
                                                tier.kv_quantize))
        kv_gb = _tree_gb(pool) / tp     # pool shards its kv-head axis
        # Parked prefix entries hold block lists inside the same pool.
        parked = 0.0
    else:
        from ..models import transformer
        kvq = tier.kv_quantize if cfg.num_experts == 1 else "none"
        cache = jax.eval_shape(
            lambda: transformer.init_kv_cache(cfg, 1, cfg.max_seq_len, kvq))
        # The cache shards its kv-head axis over tp, and — under
        # sequence-parallel decode (dense bf16 caches,
        # parallel/sp_attention.py) — its sequence axis over sp.
        sp_div = (sp if sp > 1 and cfg.num_experts == 1
                  and kvq == "none" else 1)
        kv_gb = _tree_gb(cache) / tp / sp_div
        # Each parked prefix-cache entry pins one full cache
        # (engine/prefix_cache.py, TierConfig.prefix_cache_entries) —
        # except under sequence-parallel decode, where the engine
        # disables prefix reuse (engine/inference.py _sp_shard).
        parked = (kv_gb * tier.prefix_cache_entries
                  if tier.enable_prefix_cache and sp_div == 1 else 0.0)

    total = params_gb + kv_gb + parked
    return {
        "tier": tier.name,
        "model": cfg.name,
        "chips": chips,
        "quantize": tier.quantize,
        "params_gb_per_chip": round(params_gb, 3),
        "kv_gb_per_chip": round(kv_gb + parked, 3),
        "total_gb_per_chip": round(total, 3),
        "hbm_per_chip_gb": hbm_per_chip_gb,
        # ~0.75 GB/chip headroom for activations, compiled program
        # temps and XLA's allocator slack.
        "fits": total <= hbm_per_chip_gb - 0.75,
        "headroom_gb": round(hbm_per_chip_gb - total, 3),
    }


def cluster_hbm_budget(cluster,
                       devices: Optional[Sequence[jax.Device]] = None,
                       hbm_per_chip_gb: float = DEFAULT_HBM_PER_CHIP_GB
                       ) -> Dict[str, Dict[str, Any]]:
    """Budget every local tier of ``cluster`` against the submesh
    ``carve_tier_meshes`` actually hands it.

    Tiers claim chips in declaration order, so a later tier's deployed
    tp/sp/ep can be SMALLER (bigger per-chip footprint) than the tier
    config asks for — budgeting each tier standalone against the full
    pod would miss that.  Remote tiers (``endpoint`` set) are skipped:
    their chips live on another host.
    """
    from ..parallel.mesh import carve_tier_meshes
    meshes = carve_tier_meshes(cluster, devices)
    return {tier.name: tier_hbm_budget(tier, hbm_per_chip_gb=hbm_per_chip_gb,
                                       mesh=meshes[tier.name])
            for tier in cluster.tiers() if not tier.endpoint}
