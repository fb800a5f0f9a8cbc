"""Ragged paged decode attention — one fused kernel over the whole
mixed-length batch.

The paged decode family in ``pallas_attention.py`` grids over
(slot, kv-head, table-block): each program owns one head of one slot, so
per-head DMAs are small and the grid grows with ``B × Nkv × MB`` even
though most of those programs are clamped no-ops past each slot's
frontier.  The batched engine additionally bounded the XLA gather with a
BUCKETED window rung shared across the batch (engine/batching.py), so a
tick at length skew paid the longest rung for every slot and each rung
minted its own compiled decode program.

This module is the blueprint of PAPERS.md "Ragged Paged Attention: A
High-Performance and Flexible LLM Inference Kernel for TPU" adapted to
the repo's pool layout: ONE kernel invocation serves all active slots
regardless of length skew.

- Grid is (slot, table-block) — slots × KV blocks, heads looped in VMEM.
  Each grid step DMAs pool block ``tables[b, j]`` across ALL kv heads as
  one [Nkv, bs, D] tile (of the layer's head-major view [Nkv, NB, bs,
  D], so the tile is Nkv strided (bs, D) sublane×lane planes; the
  engine's pool rests token-major and ops.attention._layer_views makes
  the view).
- Per-slot TRUE lengths: iterations past ``pos[b]`` are index-clamped
  onto the slot's frontier block (the repeated index elides the DMA) and
  compute-skipped, so a slot at position p streams ceil((p+1)/bs) blocks
  — its own length, never the batch max, never a padded bucket window.
- Online-softmax (flash) accumulation in float32 scratch: running
  max / sum / accumulator per (query-head, lane), one [Nq, bs] score
  tile per block.
- The int8 variant streams half-width pool tiles plus their per-row f32
  scales and dequantizes in VMEM — the same symmetric per-row scheme
  ``ops/quant.quantize_kv_rows`` writes (dequant is ``int8 * scale``,
  mirroring ``dequantize_kv_rows`` without ever materializing the
  dequantized pool in HBM).

Both kernels run in interpreter mode off-TPU, so the CPU parity suite
(tests/test_ragged_parity.py) exercises the exact code paths Mosaic
compiles; the measured dispatch table decides pallas-vs-xla per shape on
hardware (``ragged_decode`` / ``ragged_decode_q8`` rows in
bench/ab_dispatch.json, written by ``ab_kernels micro``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .pallas_attention import _interpret


def _ragged_decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, bs: int, nkv: int,
                          d: int, scale: float):
    """Flash recurrence over one slot's block table, all heads per
    program: grid (B, MB), table-block index j innermost.  The pipeline
    DMAs pool block ``tables[b, j]`` across every kv head via the
    scalar-prefetched index map; heads are sliced inside VMEM and the
    per-head [G, bs] score tiles stack to one [Nq, bs] plane sharing the
    flash stats."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Per-slot frontier: blocks past THIS slot's length are index-clamped
    # onto its frontier block (DMA elided on the repeated index) and
    # skipped here — each slot pays for its own length, not the batch max.
    @pl.when(j * bs <= pos_ref[b])
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale             # [Nq, D]
        groups = q.shape[0] // nkv

        # Per-head scores, stacked back to [Nq, bs] (row r ↔ head r//G).
        s = jnp.concatenate([
            jax.lax.dot_general(
                q[h * groups:(h + 1) * groups],
                k_ref[h, 0].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, bs]
            for h in range(nkv)], axis=0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        s = jnp.where(col <= pos_ref[b], s, NEG_INF)         # ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[h * groups:(h + 1) * groups].astype(v_ref.dtype),
                    v_ref[h, 0],
                    preferred_element_type=jnp.float32)      # [G, D]
            for h in range(nkv)], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def ragged_paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, tables: jax.Array,
                                  pos: jax.Array) -> jax.Array:
    """Batched ragged decode attention over a paged KV pool
    (engine/paged_kv.py head-major layout): q [B, Nq, D], pools
    [Nkv, NB, bs, D], tables [B, MB] pool block ids, pos [B] per-slot
    TRUE positions -> [B, Nq, D].

    One invocation serves the whole mixed-length batch: logical position
    p of slot b lives at pool cell ``(h, tables[b, p // bs], p % bs)``,
    and the in-kernel frontier clamp means a slot streams exactly its
    own ceil((pos+1)/bs) blocks.  Callers pass the FULL table row — the
    padding that the XLA fallback must gather costs this kernel nothing,
    so the batched engine compiles ONE decode program for its whole
    life instead of one per bucketed window rung."""
    b, nq, d = q.shape
    nkv, bs = k_pool.shape[0], k_pool.shape[2]
    mb = tables.shape[1]

    tables32 = tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)

    kernel = functools.partial(_ragged_decode_kernel, bs=bs, nkv=nkv, d=d,
                               scale=d ** -0.5)

    def kv_index(b_, j, tbl, p):
        # Clamp to the slot's frontier block: overshoot iterations repeat
        # the previous index, so their DMA is elided and their compute is
        # pl.when-skipped in the kernel.
        return (0, tbl[b_, jnp.minimum(j, p[b_] // bs)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, nq, d), lambda b_, j, tbl, p: (b_, 0, 0)),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, nq, d), lambda b_, j, tbl, p: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq, d), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
    )(tables32, pos32, q, k_pool, v_pool)


def _ragged_verify_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, bs: int, nkv: int,
                          d: int, g: int, scale: float):
    """Speculative-verify twin of ``_ragged_decode_kernel``: each slot
    carries ``g`` query positions (the γ+1 verify chunk) instead of one.
    The q tile arrives head-major flattened ([Nq·g, D], position index
    fastest within each head's row group), so the per-head score stacks
    are the decode kernel's with ``groups·g`` rows, and the ragged mask
    becomes per-ROW: row r (position ``r % g`` of its slot) sees
    ``col <= pos[b] + r % g``.  The frontier clamp streams to the LAST
    query's block, so a slot still pays ceil((pos+g)/bs) blocks — its
    own length plus its chunk, never the batch max."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    last = pos_ref[b] + g - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs <= last)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale             # [Nq·g, D]
        groups = q.shape[0] // (nkv * g)

        s = jnp.concatenate([
            jax.lax.dot_general(
                q[h * groups * g:(h + 1) * groups * g],
                k_ref[h, 0].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G·g, bs]
            for h in range(nkv)], axis=0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        row_pos = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % g
                   + pos_ref[b])
        s = jnp.where(col <= row_pos, s, NEG_INF)        # per-row ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[h * groups * g:(h + 1) * groups * g
                      ].astype(v_ref.dtype),
                    v_ref[h, 0],
                    preferred_element_type=jnp.float32)
            for h in range(nkv)], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def ragged_paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, tables: jax.Array,
                                  pos: jax.Array) -> jax.Array:
    """Batched ragged VERIFY attention over a paged KV pool: q
    [B, G, Nq, D] — the γ+1 speculative verify chunk per slot, queries
    at absolute positions ``pos[b] + g`` — pools [Nkv, NB, bs, D],
    tables [B, MB], pos [B] the FIRST query's position -> [B, G, Nq, D].

    One invocation verifies every slot's drafts regardless of length
    skew: the same per-slot frontier clamp as the decode kernel, widened
    to the last query's block, with a per-query causal mask so draft g
    attends exactly its own prefix (prefix + chunk positions <= pos+g,
    all already written — write-before-attend, like decode)."""
    b, g, nq, d = q.shape
    nkv, bs = k_pool.shape[0], k_pool.shape[2]
    mb = tables.shape[1]

    tables32 = tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)
    # Head-major flatten: row (h_q·g + position) so each kv head's rows
    # are contiguous and the in-kernel per-head slicing stays the decode
    # kernel's.
    qf = q.transpose(0, 2, 1, 3).reshape(b, nq * g, d)

    kernel = functools.partial(_ragged_verify_kernel, bs=bs, nkv=nkv, d=d,
                               g=g, scale=d ** -0.5)

    def kv_index(b_, j, tbl, p):
        return (0, tbl[b_, jnp.minimum(j, (p[b_] + g - 1) // bs)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, nq * g, d), lambda b_, j, tbl, p: (b_, 0, 0)),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, nq * g, d),
                               lambda b_, j, tbl, p: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq * g, d), jnp.float32),
            pltpu.VMEM((nq * g, 1), jnp.float32),
            pltpu.VMEM((nq * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        interpret=_interpret(),
    )(tables32, pos32, qf, k_pool, v_pool)
    return out.reshape(b, nq, g, d).transpose(0, 2, 1, 3)


def _ragged_decode_kernel_q8(tables_ref, pos_ref, q_ref, k_ref, v_ref,
                             ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref,
                             *, bs: int, nkv: int, d: int, scale: float):
    """int8 twin of _ragged_decode_kernel: pool blocks arrive as int8
    [Nkv, bs, D] tiles (half-width DMA) plus per-row f32 scale planes
    [Nkv, bs, 1]; dequantization (``int8 * scale``, the
    ops/quant.dequantize_kv_rows contract) happens in VMEM — the HBM
    read is what shrinks."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs <= pos_ref[b])
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale             # [Nq, D]
        groups = q.shape[0] // nkv

        def dq(ref, sref, h):
            return ref[h, 0].astype(jnp.float32) * sref[h, 0]  # [bs, D]

        s = jnp.concatenate([
            jax.lax.dot_general(
                q[h * groups:(h + 1) * groups], dq(k_ref, ks_ref, h),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, bs]
            for h in range(nkv)], axis=0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        s = jnp.where(col <= pos_ref[b], s, NEG_INF)         # ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[h * groups:(h + 1) * groups], dq(v_ref, vs_ref, h),
                    preferred_element_type=jnp.float32)      # [G, D]
            for h in range(nkv)], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def ragged_paged_decode_attention_q8(q: jax.Array, k_pool: jax.Array,
                                     v_pool: jax.Array, k_scale: jax.Array,
                                     v_scale: jax.Array, tables: jax.Array,
                                     pos: jax.Array) -> jax.Array:
    """``ragged_paged_decode_attention`` over an int8 pool
    (engine/paged_kv.py kv_quantize='int8'): pools [Nkv, NB, bs, D] int8,
    scales [Nkv, NB, bs] f32.  Streams half the KV bytes of the bf16
    kernel with the same per-slot frontier clamp, and never materializes
    the dequantized window in HBM (the XLA fallback's gather does)."""
    b, nq, d = q.shape
    nkv, bs = k_pool.shape[0], k_pool.shape[2]
    mb = tables.shape[1]

    tables32 = tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)
    # Scales as [Nkv, NB, bs, 1]: the trailing singleton keeps Mosaic on
    # its (sublane, lane) tiling for the tiny per-row plane.
    ks = k_scale[..., None].astype(jnp.float32)
    vs = v_scale[..., None].astype(jnp.float32)

    kernel = functools.partial(_ragged_decode_kernel_q8, bs=bs, nkv=nkv,
                               d=d, scale=d ** -0.5)

    def kv_index(b_, j, tbl, p):
        return (0, tbl[b_, jnp.minimum(j, p[b_] // bs)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, nq, d), lambda b_, j, tbl, p: (b_, 0, 0)),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
            pl.BlockSpec((nkv, 1, bs, 1), kv_index),
            pl.BlockSpec((nkv, 1, bs, 1), kv_index),
        ],
        out_specs=pl.BlockSpec((1, nq, d), lambda b_, j, tbl, p: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq, d), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
    )(tables32, pos32, q, k_pool, v_pool, ks, vs)


def _ragged_verify_kernel_q8(tables_ref, pos_ref, q_ref, k_ref, v_ref,
                             ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref,
                             *, bs: int, nkv: int, d: int, g: int,
                             scale: float):
    """int8 twin of ``_ragged_verify_kernel``: half-width pool tiles +
    per-row f32 scales, dequantized in VMEM (the ops/quant contract),
    with the verify kernel's per-row ragged mask and last-query frontier
    clamp."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    last = pos_ref[b] + g - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs <= last)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale             # [Nq·g, D]
        groups = q.shape[0] // (nkv * g)

        def dq(ref, sref, h):
            return ref[h, 0].astype(jnp.float32) * sref[h, 0]  # [bs, D]

        s = jnp.concatenate([
            jax.lax.dot_general(
                q[h * groups * g:(h + 1) * groups * g],
                dq(k_ref, ks_ref, h),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G·g, bs]
            for h in range(nkv)], axis=0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        row_pos = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % g
                   + pos_ref[b])
        s = jnp.where(col <= row_pos, s, NEG_INF)        # per-row ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[h * groups * g:(h + 1) * groups * g],
                    dq(v_ref, vs_ref, h),
                    preferred_element_type=jnp.float32)
            for h in range(nkv)], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def ragged_paged_verify_attention_q8(q: jax.Array, k_pool: jax.Array,
                                     v_pool: jax.Array, k_scale: jax.Array,
                                     v_scale: jax.Array, tables: jax.Array,
                                     pos: jax.Array) -> jax.Array:
    """``ragged_paged_verify_attention`` over an int8 pool: q
    [B, G, Nq, D], pools [Nkv, NB, bs, D] int8, scales [Nkv, NB, bs]
    f32, pos [B] first-query positions -> [B, G, Nq, D].  Streams half
    the KV bytes of the bf16 verify kernel with the same per-row mask;
    never materializes the dequantized window in HBM (the XLA fallback's
    gather does)."""
    b, g, nq, d = q.shape
    nkv, bs = k_pool.shape[0], k_pool.shape[2]
    mb = tables.shape[1]

    tables32 = tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)
    ks = k_scale[..., None].astype(jnp.float32)
    vs = v_scale[..., None].astype(jnp.float32)
    qf = q.transpose(0, 2, 1, 3).reshape(b, nq * g, d)

    kernel = functools.partial(_ragged_verify_kernel_q8, bs=bs, nkv=nkv,
                               d=d, g=g, scale=d ** -0.5)

    def kv_index(b_, j, tbl, p):
        return (0, tbl[b_, jnp.minimum(j, (p[b_] + g - 1) // bs)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, nq * g, d), lambda b_, j, tbl, p: (b_, 0, 0)),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
            pl.BlockSpec((nkv, 1, bs, d), kv_index),
            pl.BlockSpec((nkv, 1, bs, 1), kv_index),
            pl.BlockSpec((nkv, 1, bs, 1), kv_index),
        ],
        out_specs=pl.BlockSpec((1, nq * g, d),
                               lambda b_, j, tbl, p: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq * g, d), jnp.float32),
            pltpu.VMEM((nq * g, 1), jnp.float32),
            pltpu.VMEM((nq * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        interpret=_interpret(),
    )(tables32, pos32, qf, k_pool, v_pool, ks, vs)
    return out.reshape(b, nq, g, d).transpose(0, 2, 1, 3)
