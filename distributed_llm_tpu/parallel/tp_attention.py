"""Tensor-parallel Pallas prefill: shard_map the flash kernel over heads.

Round 1 left sharded tiers entirely on the XLA attention path — a
``pallas_call`` has no GSPMD partitioning rule, so opting in under a
mesh would replicate the operands (ops/attention.py resolve_impl).  But
attention is embarrassingly parallel over kv-head groups: under Megatron
sharding q/k/v are already head-sharded on the 'tp' axis, so wrapping the
flash kernel in ``shard_map`` runs one per-shard kernel per chip with
ZERO added collectives — each chip's [B, S, Nq/tp, D] slice is a complete
smaller attention problem (GQA group structure is preserved because Nq
and Nkv shard by the same factor).

That covers the FLOPs-heavy prefill.  Decode
stays on the GSPMD path under meshes: it is weight-bandwidth-bound, the
kernel win there is the frontier-clamped KV streaming, and the paged
pool's gather already shards on the kv-head axis.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import jax
from jax.sharding import PartitionSpec as P


def tp_flash_causal(mesh: jax.sharding.Mesh,
                    head_axis: str = "tp") -> Callable:
    """(q, k, v) -> out with every array [B, S, N, D] sharded on its head
    axis over ``head_axis``; runs the flash kernel per shard."""
    from jax import shard_map

    from ..ops.pallas_attention import flash_causal_attention

    spec = P(None, None, head_axis, None)
    # check_vma off: a pallas_call's abstract eval carries no varying-axis
    # info, and this wrap is manifestly per-shard (no collectives).
    return shard_map(flash_causal_attention, mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)


def tp_flash_decode(mesh: jax.sharding.Mesh,
                    head_axis: str = "tp") -> Callable:
    """(q [B,Nq,D], k/v [B,S,Nkv,D], pos [B]) -> [B,Nq,D], head-sharded:
    the KV-length-tiled flash decode kernel runs per head-shard — each
    chip streams only its own heads' frontier-clamped cache slice."""
    from jax import shard_map

    from ..ops.pallas_attention import flash_decode_attention

    qspec = P(None, head_axis, None)
    cspec = P(None, None, head_axis, None)
    return shard_map(flash_decode_attention, mesh=mesh,
                     in_specs=(qspec, cspec, cspec, P(None)),
                     out_specs=qspec, check_vma=False)


def tp_paged_decode(mesh: jax.sharding.Mesh, quantized: bool = False,
                    head_axis: str = "tp") -> Callable:
    """Paged-pool twin: pools [Nkv, NB, bs, D] (+ scale planes when
    ``quantized``) shard on the kv-head axis — exactly the batched
    engine's pool sharding (parallel/sharding.py kv_pool_specs) — so the
    in-kernel block walk is shard-local.  Signature matches the
    decode_step_paged attention hook: (q, k_pool, v_pool, tables, pos,
    k_scale, v_scale)."""
    from jax import shard_map

    from ..ops.pallas_attention import (paged_decode_attention,
                                        paged_decode_attention_q8)

    qspec = P(None, head_axis, None)
    pspec = P(head_axis, None, None, None)
    if quantized:
        sspec = P(head_axis, None, None)
        fn = shard_map(
            lambda q, kp, vp, ks, vs, tbl, pos: paged_decode_attention_q8(
                q, kp, vp, ks, vs, tbl, pos),
            mesh=mesh,
            in_specs=(qspec, pspec, pspec, sspec, sspec, P(None), P(None)),
            out_specs=qspec, check_vma=False)
        return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, ks, vs,
                                                      tbl, pos)
    fn = shard_map(paged_decode_attention, mesh=mesh,
                   in_specs=(qspec, pspec, pspec, P(None), P(None)),
                   out_specs=qspec, check_vma=False)
    return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, tbl, pos)


def tp_ragged_decode(mesh: jax.sharding.Mesh, impl: str = "auto",
                     quantized: bool = False,
                     head_axis: str = "tp") -> Callable:
    """Shard-mapped RAGGED paged decode (PR 16): wraps the DISPATCHING
    ``ops.attention.ragged_decode`` — not a fixed kernel — over the
    kv-head axis, so each shard re-runs the pallas-vs-xla dispatch on its
    own whole-head slice (fused ragged kernel on TPU, gather fallback on
    CPU) and the combine is a head concat via ``out_specs``, never a
    softmax merge.  Signature matches the decode_step_paged /
    verify_step_paged attention hook: (q, k_pool, v_pool, tables, pos,
    k_scale, v_scale) with per-layer pools [Nkv, NB, bs, D]."""
    from jax import shard_map

    from ..ops import attention

    qspec = P(None, head_axis, None)
    pspec = P(head_axis, None, None, None)
    if quantized:
        sspec = P(head_axis, None, None)
        fn = shard_map(
            lambda q, kp, vp, ks, vs, tbl, pos: attention.ragged_decode(
                q, kp, vp, tbl, pos, impl=impl, k_scale=ks, v_scale=vs),
            mesh=mesh,
            in_specs=(qspec, pspec, pspec, sspec, sspec,
                      P(None, None), P(None)),
            out_specs=qspec, check_vma=False)
        return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, ks, vs,
                                                      tbl, pos)
    fn = shard_map(
        lambda q, kp, vp, tbl, pos: attention.ragged_decode(
            q, kp, vp, tbl, pos, impl=impl),
        mesh=mesh,
        in_specs=(qspec, pspec, pspec, P(None, None), P(None)),
        out_specs=qspec, check_vma=False)
    return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, tbl, pos)


def tp_ragged_verify(mesh: jax.sharding.Mesh, impl: str = "auto",
                     quantized: bool = False,
                     head_axis: str = "tp") -> Callable:
    """Shard-mapped RAGGED speculative verify: q [B, G, Nq, D] sharded on
    its head axis, pools on the kv-head axis — the γ+1-query twin of
    ``tp_ragged_decode`` so a spec round verifies every slot's drafts in
    ONE fused sharded call.  Same hook signature."""
    from jax import shard_map

    from ..ops import attention

    qspec = P(None, None, head_axis, None)
    pspec = P(head_axis, None, None, None)
    if quantized:
        sspec = P(head_axis, None, None)
        fn = shard_map(
            lambda q, kp, vp, ks, vs, tbl, pos: attention.ragged_verify(
                q, kp, vp, tbl, pos, impl=impl, k_scale=ks, v_scale=vs),
            mesh=mesh,
            in_specs=(qspec, pspec, pspec, sspec, sspec,
                      P(None, None), P(None)),
            out_specs=qspec, check_vma=False)
        return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, ks, vs,
                                                      tbl, pos)
    fn = shard_map(
        lambda q, kp, vp, tbl, pos: attention.ragged_verify(
            q, kp, vp, tbl, pos, impl=impl),
        mesh=mesh,
        in_specs=(qspec, pspec, pspec, P(None, None), P(None)),
        out_specs=qspec, check_vma=False)
    return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, tbl, pos)


def tp_local_ragged_decode(mesh: jax.sharding.Mesh, impl: str = "auto",
                           quantized: bool = False) -> Callable:
    """ALL-REPLICATED shard_map wrap of the dispatching ragged decode:
    every chip runs the FULL problem on its own replica (in/out specs
    all ``P(None, ...)``), so a REPLICATED draft model drafts locally
    with zero collectives — and the per-device dispatcher may still
    pick the fused Pallas kernel, which is illegal in a plain jit over
    a mesh but fine inside shard_map's per-device region.  Hook
    signature matches ``tp_ragged_decode``."""
    from jax import shard_map

    from ..ops import attention

    qspec = P(None, None, None)
    pspec = P(None, None, None, None)
    if quantized:
        sspec = P(None, None, None)
        fn = shard_map(
            lambda q, kp, vp, ks, vs, tbl, pos: attention.ragged_decode(
                q, kp, vp, tbl, pos, impl=impl, k_scale=ks, v_scale=vs),
            mesh=mesh,
            in_specs=(qspec, pspec, pspec, sspec, sspec,
                      P(None, None), P(None)),
            out_specs=qspec, check_vma=False)
        return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, ks, vs,
                                                      tbl, pos)
    fn = shard_map(
        lambda q, kp, vp, tbl, pos: attention.ragged_decode(
            q, kp, vp, tbl, pos, impl=impl),
        mesh=mesh,
        in_specs=(qspec, pspec, pspec, P(None, None), P(None)),
        out_specs=qspec, check_vma=False)
    return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, tbl, pos)


def _tp_ragged_ok(mesh: Optional[jax.sharding.Mesh], cfg) -> bool:
    """Gate for the shard-mapped ragged hooks: tp-only mesh, dense model,
    divisible q AND kv heads.  Deliberately NOT pallas-gated — the
    dispatcher inside the shard re-decides pallas-vs-xla per shard, so
    the wrap is correct (and byte-identical to tp=1) on any backend."""
    if mesh is None or cfg.num_experts > 1:
        return False
    shape = dict(mesh.shape)
    tp = shape.get("tp", 1)
    if tp <= 1 or shape.get("sp", 1) > 1 or shape.get("ep", 1) > 1:
        return False
    return not (cfg.num_kv_heads % tp or cfg.num_heads % tp)


def tp_ragged_decode_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                          quantized: bool = False) -> Optional[Callable]:
    """Ragged decode hook for TP tiers, or None (unsharded / non-tp)."""
    if not _tp_ragged_ok(mesh, cfg):
        return None
    return tp_ragged_decode(mesh, impl=cfg.attention_impl,
                            quantized=quantized)


def tp_ragged_verify_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                          quantized: bool = False) -> Optional[Callable]:
    """Ragged verify hook for TP tiers, or None."""
    if not _tp_ragged_ok(mesh, cfg):
        return None
    return tp_ragged_verify(mesh, impl=cfg.attention_impl,
                            quantized=quantized)


def _tp_policy(mesh: Optional[jax.sharding.Mesh], cfg, kind: str,
               length: int) -> bool:
    """Shared gate for every shard-mapped Pallas hook: tp-only mesh,
    dense model, divisible heads, Pallas preferred for (kind, length)."""
    if mesh is None or cfg.num_experts > 1:
        return False
    shape = dict(mesh.shape)
    tp = shape.get("tp", 1)
    if tp <= 1 or shape.get("sp", 1) > 1:
        return False
    if cfg.num_kv_heads % tp or cfg.num_heads % tp:
        return False
    env = os.environ.get("DLLM_ATTENTION")
    if env == "xla":
        return False
    if env != "pallas" and jax.default_backend() != "tpu":
        return False
    from ..ops.attention import _choose
    return _choose("pallas", kind, length) == "pallas"


def tp_decode_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                   cache_len: int) -> Optional[Callable]:
    """Decode hook for TP tiers with a contiguous cache, or None for the
    GSPMD XLA path."""
    if not _tp_policy(mesh, cfg, "decode", cache_len):
        return None
    return tp_flash_decode(mesh)


def tp_paged_decode_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                         window: int,
                         quantized: bool = False) -> Optional[Callable]:
    """Decode hook for TP tiers over the paged pool, or None."""
    kind = "paged_decode_q8" if quantized else "paged_decode"
    if not _tp_policy(mesh, cfg, kind, window):
        return None
    return tp_paged_decode(mesh, quantized)


def tp_prefill_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                    bucket: int) -> Optional[Callable]:
    """Policy twin of engine upgrade_attention_impl for TP meshes: the
    shard-mapped flash prefill when (a) the mesh is tensor-parallel only
    (ring attention owns sp prefill), (b) the model is dense with
    tp-divisible kv heads and a block-aligned bucket, and (c) Pallas is
    the preferred prefill impl — TPU backend or an explicit
    DLLM_ATTENTION=pallas, minus dispatch-table demotions
    (ops/attention.py).  None = stay on the GSPMD XLA path."""
    if bucket % min(bucket, 128):
        return None                       # flash kernel block contract
    if not _tp_policy(mesh, cfg, "prefill", bucket):
        return None
    return tp_flash_causal(mesh)
