"""Sequence-parallel decode attention: the KV cache sharded over 'sp'.

Ring attention (parallel/ring_attention.py) spreads PREFILL's O(S²) over
the sp axis; until round 3 decode then fell back to a fully replicated
cache — every chip held and streamed the WHOLE context every step, so an
sp tier's context capacity was still one chip's HBM.  Here the cache
keeps its sequence axis sharded over 'sp' (parallel/sharding.py
kv_cache_specs sp_axis) and each decode step is a flash-style two-phase
reduction:

  1. per shard: masked attention partials over the LOCAL S/sp cache
     positions — running max ``m_i``, normalizer ``l_i``, unnormalized
     value sum ``o_i`` (float32, like ops/attention.py's softmax);
  2. across shards: one ``pmax`` + two ``psum`` over 'sp' merge the
     partials exactly (log-sum-exp algebra), then normalize.

Per chip that is S/sp cached positions held AND streamed per step — both
HBM capacity and decode's KV read traffic scale with sp, at the cost of
three tiny [B, N]-shaped collectives per layer riding the ICI.

Composes with tensor parallelism: q and the cache shard their head axes
over 'tp' exactly as without sp (the reduction only touches 'sp').
The reference has no analogue — its context lives inside Ollama on one
board (SURVEY.md §5.7).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _partials(q, k, v, pos, offset):
    """Masked attention partials of q against the local cache slice whose
    global positions start at ``offset``.  Returns (m [B,N], l [B,N],
    o [B,N,D] unnormalized, all float32)."""
    from ..ops.attention import NEG_INF, _expand_kv
    groups = q.shape[1] // k.shape[2]
    k = _expand_kv(k, groups)
    v = _expand_kv(v, groups)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bnd,bknd->bnk", q, k).astype(jnp.float32) * scale
    s_local = k.shape[1]
    valid = (offset + jnp.arange(s_local))[None, :] <= pos[:, None]  # [B,S_l]
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                                     # [B,N]
    e = jnp.exp(logits - m[..., None])
    # An all-masked shard has m == NEG_INF and e == exp(0) == 1 rows:
    # zero them so the shard contributes nothing (its exp(m - m_global)
    # weight is 0 anyway, but l/o must not carry garbage).
    e = jnp.where(valid[:, None, :], e, 0.0)
    l = jnp.sum(e, axis=-1)                                          # [B,N]
    o = jnp.einsum("bnk,bknd->bnd", e.astype(v.dtype),
                   v).astype(jnp.float32)
    return m, l, o


def sp_flash_decode(mesh: jax.sharding.Mesh, sp_axis: str = "sp",
                    head_axis: Optional[str] = None) -> Callable:
    """(q [B,Nq,D], k/v [B,S,Nkv,D] sequence-sharded, pos [B]) ->
    [B,Nq,D]: per-shard partials + exact log-sum-exp merge over 'sp'.
    ``head_axis`` additionally shards the head axes over 'tp'."""
    from jax import shard_map

    def local(q, k_shard, v_shard, pos):
        s_local = k_shard.shape[1]
        offset = jax.lax.axis_index(sp_axis) * s_local
        m_i, l_i, o_i = _partials(q, k_shard, v_shard, pos, offset)
        m = jax.lax.pmax(m_i, sp_axis)
        c = jnp.exp(m_i - m)
        l = jax.lax.psum(l_i * c, sp_axis)
        o = jax.lax.psum(o_i * c[..., None], sp_axis)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    h = head_axis
    qspec = P(None, h, None)
    cspec = P(None, sp_axis, h, None)
    return shard_map(local, mesh=mesh,
                     in_specs=(qspec, cspec, cspec, P(None)),
                     out_specs=qspec, check_vma=False)


def sp_decode_attn(mesh: Optional[jax.sharding.Mesh], cfg,
                   cache_len: int) -> Optional[Callable]:
    """Decode hook for sequence-parallel tiers (engine/inference.py
    decode_kw["attn"]), or None to stay on the replicated GSPMD path.
    Dense bf16 caches only; the cache length must shard evenly."""
    if mesh is None or cfg.num_experts > 1:
        return None
    shape = dict(mesh.shape)
    sp = shape.get("sp", 1)
    if sp <= 1 or cache_len % sp:
        return None
    tp = shape.get("tp", 1)
    if tp > 1 and (cfg.num_kv_heads % tp or cfg.num_heads % tp):
        return None
    return sp_flash_decode(mesh, "sp", head_axis="tp" if tp > 1 else None)
