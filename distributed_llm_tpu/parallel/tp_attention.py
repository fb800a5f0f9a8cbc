"""Tensor-parallel attention hooks: shard_map over the head axis.

A ``pallas_call`` has no GSPMD partitioning rule, so opting in under a
mesh would replicate the operands (ops/attention.py resolve_impl).  But
attention is embarrassingly parallel over kv-head groups: under Megatron
sharding q/k/v are already head-sharded on the 'tp' axis, so wrapping the
flash prefill kernel in ``shard_map`` runs one per-shard kernel per chip
with ZERO added collectives — each chip's [B, S, Nq/tp, D] slice is a
complete smaller attention problem (GQA group structure is preserved
because Nq and Nkv shard by the same factor).

That covers the FLOPs-heavy prefill.  Decode over the paged pool has two
shapes under a mesh: the fused tick's hooks below (``tp_ragged_*``) wrap
the ops of ops/attention.py over per-shard head-major views, and the
windowed tick stays on the GSPMD XLA path (the pool's gather already
shards on the kv-head axis).
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


def _paged_hook(op: Callable, mesh: jax.sharding.Mesh, qspec: P,
                head_axis: Optional[str], quantized: bool) -> Callable:
    """A paged op of ops/attention.py under shard_map, as the attention
    hook of decode_step_paged / verify_step_paged: (q, k_pool, v_pool,
    tables, pos, k_scale, v_scale) with per-layer head-major pools [Nkv,
    NB, bs, D] (scales [Nkv, NB, bs]) on ``head_axis``, tables and
    positions replicated."""
    from jax import shard_map

    pspec = P(head_axis, None, None, None)
    sspec = P(head_axis, None, None)
    where = (P(None, None), P(None))
    if quantized:
        fn = shard_map(
            lambda q, kp, vp, ks, vs, tbl, pos: op(
                q, kp, vp, tbl, pos, k_scale=ks, v_scale=vs),
            mesh=mesh, in_specs=(qspec, pspec, pspec, sspec, sspec, *where),
            out_specs=qspec, check_vma=False)
        return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, ks, vs,
                                                      tbl, pos)
    fn = shard_map(
        lambda q, kp, vp, tbl, pos: op(q, kp, vp, tbl, pos),
        mesh=mesh, in_specs=(qspec, pspec, pspec, *where),
        out_specs=qspec, check_vma=False)
    return lambda q, kp, vp, tbl, pos, ks, vs: fn(q, kp, vp, tbl, pos)


def tp_ragged_decode(mesh: jax.sharding.Mesh,
                     quantized: bool = False,
                     head_axis: str = "tp") -> Callable:
    """Shard-mapped fused-tick paged decode (PR 16): wraps
    ``ops.attention.paged_decode`` over the kv-head axis, so each shard
    gathers and attends its own whole-head slice and the combine is a
    head concat via ``out_specs``, never a softmax merge."""
    from ..ops import attention
    return _paged_hook(attention.paged_decode, mesh,
                       P(None, head_axis, None), head_axis, quantized)


def tp_ragged_verify(mesh: jax.sharding.Mesh,
                     quantized: bool = False,
                     head_axis: str = "tp") -> Callable:
    """Shard-mapped RAGGED speculative verify: q [B, G, Nq, D] sharded on
    its head axis, pools on the kv-head axis — the γ+1-query twin of
    ``tp_ragged_decode`` so a spec round verifies every slot's drafts in
    ONE fused sharded call."""
    from ..ops import attention
    return _paged_hook(attention.ragged_verify, mesh,
                       P(None, None, head_axis, None), head_axis, quantized)


def tp_local_ragged_decode(mesh: jax.sharding.Mesh,
                           quantized: bool = False) -> Callable:
    """ALL-REPLICATED shard_map wrap of the fused-tick paged decode:
    every chip runs the FULL problem on its own replica (in/out specs
    all ``P(None, ...)``), so a REPLICATED draft model drafts locally
    with zero collectives."""
    from ..ops import attention
    return _paged_hook(attention.paged_decode, mesh, P(None, None, None),
                       None, quantized)


def _tp_ragged_ok(mesh: Optional[jax.sharding.Mesh], cfg) -> bool:
    """Gate for the shard-mapped ragged hooks: tp-only mesh, dense model,
    divisible q AND kv heads.  Deliberately NOT pallas-gated: the ops
    inside the shard are XLA, so the wrap is correct (and byte-identical
    to tp=1) on any backend."""
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
    return tp_ragged_decode(mesh, quantized=quantized)


def tp_ragged_verify_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                          quantized: bool = False) -> Optional[Callable]:
    """Ragged verify hook for TP tiers, or None."""
    if not _tp_ragged_ok(mesh, cfg):
        return None
    return tp_ragged_verify(mesh, quantized=quantized)


def tp_paged_decode_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                         window: int,
                         quantized: bool = False) -> Optional[Callable]:
    """The windowed tick's hook under a mesh: always None, the GSPMD XLA
    path (the per-head-shard paged kernel it once returned never served
    on the chip and went with ISSUE 49).  The name stays because
    ``benchmark/correct.py`` imports it and a ``simplicity`` PR may not
    edit ``benchmark/``; it goes with ROADMAP C0."""
    return None


def tp_prefill_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                    bucket: int) -> Optional[Callable]:
    """Policy twin of engine upgrade_attention_impl for TP meshes: the
    shard-mapped flash prefill when (a) the mesh is tensor-parallel only
    (ring attention owns sp prefill), (b) the model is dense with
    tp-divisible heads and a block-aligned bucket, and (c) the backend is
    the TPU or DLLM_ATTENTION=pallas forces it (=xla forbids it).  None =
    stay on the GSPMD XLA path."""
    if bucket % min(bucket, 128):
        return None                       # flash kernel block contract
    if mesh is None or cfg.num_experts > 1:
        return None
    shape = dict(mesh.shape)
    tp = shape.get("tp", 1)
    if tp <= 1 or shape.get("sp", 1) > 1:
        return None
    if cfg.num_kv_heads % tp or cfg.num_heads % tp:
        return None
    env = os.environ.get("DLLM_ATTENTION")
    if env == "xla" or (env != "pallas" and jax.default_backend() != "tpu"):
        return None
    return tp_flash_causal(mesh)
