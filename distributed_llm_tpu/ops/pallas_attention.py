"""Pallas TPU attention kernels — the hot ops of the serving engine.

The reference's attention lives inside llama.cpp's CUDA/CPU kernels behind
Ollama (SURVEY.md §2.1); these are their TPU-native replacement, written
against the Mosaic/Pallas TPU programming model (/opt/skills/guides/
pallas_guide.md):

- ``flash_causal_attention`` — blocked prefill attention with the online-
  softmax (flash) recurrence: KV blocks stream through VMEM, the [S, S]
  score matrix is never materialized in HBM, and the causal frontier prunes
  whole KV blocks (block j is skipped entirely once j*BK > (i+1)*BQ).
  float32 running max / sum / accumulator, bfloat16 everywhere else — the
  MXU-native mix.  A custom VJP recomputes attention with the XLA path on
  the backward pass so the same kernel serves training (flash backward
  trades FLOPs for the O(S²) residuals it refuses to store).
- ``flash_decode_attention`` — single-token decode against the full KV
  cache: grid over (batch, kv-head), each program attends one GQA group's
  queries to its kv head's [S_max, D] cache slice in VMEM with the
  per-sequence length mask applied in-kernel.  This is the masked/"ragged"
  decode read: every sequence sees exactly its own prefix.

Both kernels run in interpreter mode off-TPU, so the CPU test suite
exercises the exact kernel code paths the TPU compiles.

Layouts: the public contracts match ops/attention.py ([B, S, N, D] /
cache [B, S_max, N_kv, D]); kernels internally use head-major [B, N, S, D]
so the last two dims tile onto (sublane, lane).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, causal_attention, decode_attention

# Bump when any kernel IMPLEMENTATION changes: a dispatch table measured
# against older kernels is stale (ops/attention.dispatch_provenance says
# so) until bench/ab_kernels.py's micro A/B rewrites it.  Gen 2 = the in-place
# serving-layout decode/chunk kernels (the gen-1 family transposed the
# cache per call — see _decode_kernel).
KERNEL_GEN = 2


def _interpret() -> bool:
    """Interpret mode is for the host CPU only (the test suite).  On the
    TPU the kernels compile; on any other backend there is no kernel to
    run, and interpreting one in silence would pass for it — raise."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on backend {backend!r}: they "
        f"compile on 'tpu' and are interpreted on 'cpu' only")


def kernel_mode() -> str:
    """'compiled' | 'interpret' — what a pallas_call of this package
    does on the running backend (logged at engine start)."""
    return "interpret" if _interpret() else "compiled"


# =============================================================================
# Prefill: blocked causal flash attention
# =============================================================================

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  head_dim: int, scale: float):
    i = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # [BQ, D]
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq

    acc = jnp.zeros((bq, head_dim), jnp.float32)
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]                # [BK, D]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        s = jnp.where(col <= row, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                               # [BQ, BK]
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l

    # Causal pruning: KV blocks strictly above this Q block's last row
    # contribute nothing — don't even stream them in.
    n_blocks = pl.cdiv((i + 1) * bq, bk)
    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc, m, l))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    b, s, nq, d = q.shape
    nkv = k.shape[2]
    groups = nq // nkv
    bq = bk = min(s, 128)
    if s % bq != 0:
        raise ValueError(
            f"flash_causal_attention: seq len {s} not a multiple of the "
            f"{bq} block — use power-of-two buckets/seq lens (or impl='xla')")

    qh = q.transpose(0, 2, 1, 3)                             # [B, Nq, S, D]
    kh = k.transpose(0, 2, 1, 3)                             # [B, Nkv, S, D]
    vh = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, head_dim=d,
                               scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(b, nq, s // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, s, d), lambda b_, h, i: (b_, h // groups, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, s, d), lambda b_, h, i: (b_, h // groups, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=_interpret(),
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)                         # [B, S, Nq, D]


@jax.custom_vjp
def flash_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array
                           ) -> jax.Array:
    """Drop-in for ops.attention.causal_attention (q [B,S,Nq,D],
    k/v [B,S,Nkv,D] -> [B,S,Nq,D]), flash-blocked on TPU."""
    return _flash_forward(q, k, v)


def _flash_fwd(q, k, v):
    return _flash_forward(q, k, v), (q, k, v)


def _flash_bwd(res, g):
    # Backward = VJP of the mathematically identical XLA attention,
    # recomputed from the saved inputs (no O(S²) residuals kept).
    q, k, v = res
    _, vjp = jax.vjp(causal_attention, q, k, v)
    return vjp(g)


flash_causal_attention.defvjp(_flash_fwd, _flash_bwd)


# =============================================================================
# Chunked prefill: a block of suffix queries against the cache window
# =============================================================================

def _chunk_kernel_native(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                         m_ref, l_ref, *, bq: int, bk: int, nq: int,
                         nkv: int, d: int, scale: float):
    """In-place small-chunk kernel: grid (B, S_c/bq, W/bk), KV slabs in
    the serving layout ([bk, Nkv·D] — no head-major transpose/copy, see
    _decode_kernel), heads looped in VMEM with per-head flash stats
    lane-sliced out of (bq, Nq) scratch planes.  Query row r attends
    cache cols ≤ start + r; window blocks entirely past this query
    block's frontier are index-clamped (DMA elided) and skipped — an
    upgrade over the wide kernel, which masks but still streams them.
    Used for the latency-critical suffix sizes (S_c ≤ 256), where the
    window read is the whole cost and the wide kernel's cache transpose
    tripled it."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    start = pos_ref[b]
    groups = nq // nkv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk <= start + (i + 1) * bq - 1)
    def _accumulate():
        row_pos = start + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0)
        kv_k = k_ref[0]                                      # [bk, Nkv·D]
        kv_v = v_ref[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        mask = col <= row_pos
        for h in range(nq):
            hk = h // groups
            qh = q_ref[0][:, h * d:(h + 1) * d].astype(jnp.float32) * scale
            s = jax.lax.dot_general(
                qh, kv_k[:, hk * d:(hk + 1) * d].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [bq, bk]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[:, h:h + 1]
            l_prev = l_ref[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[:, h:h + 1] = m_new
            l_ref[:, h:h + 1] = l_prev * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[:, h * d:(h + 1) * d] = (
                acc_ref[:, h * d:(h + 1) * d] * alpha
                + jnp.dot(p.astype(kv_v.dtype),
                          kv_v[:, hk * d:(hk + 1) * d],
                          preferred_element_type=jnp.float32))

    @pl.when(j == nb - 1)
    def _done():
        for h in range(nq):
            o_ref[0, :, h * d:(h + 1) * d] = (
                acc_ref[:, h * d:(h + 1) * d]
                / jnp.maximum(l_ref[:, h:h + 1], 1e-30)).astype(o_ref.dtype)


def _chunk_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  head_dim: int, scale: float, w: int):
    """Flash recurrence over the cache window with a PER-QUERY frontier:
    query row r attends cache cols ≤ start + r (its absolute position),
    which covers both the reclaimed prefix and the chunk's own causal part
    — the suffix-prefill twin of _flash_kernel's block-causal mask.
    Positions are reconstructed from the per-sequence scalar start (SMEM
    allows only scalar loads on TPU); the public wrapper enforces the
    contiguity this assumes.  This WIDE variant (head-major transpose
    outside, whole-window blocks with DMA elision across heads) serves
    LARGE chunks, where attention compute amortizes the transpose;
    small suffix chunks take _chunk_kernel_native instead."""
    i = pl.program_id(2)
    # Whole [B, 1] array in SMEM; scalar-load this sequence's start.
    start = pos_ref[pl.program_id(0), 0]
    q = q_ref[0, 0].astype(jnp.float32) * scale              # [BQ, D]
    # Absolute position of each query row in this block.
    row_pos = start + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, 1), 0)

    acc = jnp.zeros((bq, head_dim), jnp.float32)
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]                # [BK, D]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        s = jnp.where(col <= row_pos, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, m, l = jax.lax.fori_loop(0, w // bk, body, (acc, m, l))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_chunk_attention(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array,
                          q_positions: jax.Array) -> jax.Array:
    """Drop-in for ops.attention.chunk_attention (q [B,S_c,Nq,D], caches
    [B,W,Nkv,D] — the caller's bucketed window slice — q_positions [B,S_c]
    -> [B,S_c,Nq,D]).

    CONTRACT beyond the XLA version: positions must be CONTIGUOUS per
    sequence (row r at q_positions[:, 0] + r) — the kernel reconstructs
    them from the scalar start, since TPU SMEM only loads scalars.  This
    holds for every chunked-prefill caller; rows whose clamped position in
    chunk_prefill differs (right padding past true_len) get a wider
    frontier here, which only affects their never-read outputs.

    Two regimes: suffix-sized chunks (S_c ≤ 256 — the multi-turn
    prefix-reuse hot path) are pure window-bandwidth and run the
    in-place native-layout kernel (no cache transpose); larger chunks
    (chunked long prefill) amortize the transpose over O(S_c·W) compute
    and keep the wide whole-window kernel, whose per-head window DMA is
    elided across heads."""
    b, s_c, nq, d = q.shape
    w, nkv = k_cache.shape[1], k_cache.shape[2]
    groups = nq // nkv
    bq = min(s_c, 128)
    bk = min(w, 128)
    if s_c % bq or w % bk:
        raise ValueError(
            f"flash_chunk_attention: chunk {s_c} / window {w} not multiples "
            f"of the ({bq}, {bk}) blocks — use power-of-two buckets")

    if s_c <= 256:
        kf = k_cache.reshape(b, w, nkv * d)      # free: contiguous dims
        vf = v_cache.reshape(b, w, nkv * d)
        qf = q.reshape(b, s_c, nq * d)
        starts = q_positions[:, 0].astype(jnp.int32)         # [B]
        kernel = functools.partial(_chunk_kernel_native, bq=bq, bk=bk,
                                   nq=nq, nkv=nkv, d=d, scale=d ** -0.5)

        def kv_index(b_, i, j, p):
            # Clamp past-frontier window blocks onto this query block's
            # frontier: repeated index elides the DMA, pl.when skips
            # the compute.
            return (b_, jnp.minimum(j, (p[b_] + (i + 1) * bq - 1) // bk), 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s_c // bq, w // bk),
            in_specs=[
                pl.BlockSpec((1, bq, nq * d),
                             lambda b_, i, j, p: (b_, i, 0)),
                pl.BlockSpec((1, bk, nkv * d), kv_index),
                pl.BlockSpec((1, bk, nkv * d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, bq, nq * d),
                                   lambda b_, i, j, p: (b_, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, nq * d), jnp.float32),
                pltpu.VMEM((bq, nq), jnp.float32),
                pltpu.VMEM((bq, nq), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
            interpret=_interpret(),
        )(starts, qf, kf, vf)
        return out.reshape(b, s_c, nq, d)

    qh = q.transpose(0, 2, 1, 3)                             # [B, Nq, S_c, D]
    kh = k_cache.transpose(0, 2, 1, 3)                       # [B, Nkv, W, D]
    vh = v_cache.transpose(0, 2, 1, 3)
    start32 = q_positions[:, :1].astype(jnp.int32)           # [B, 1] scalars

    kernel = functools.partial(_chunk_kernel, bq=bq, bk=bk, head_dim=d,
                               scale=d ** -0.5, w=w)
    out = pl.pallas_call(
        kernel,
        grid=(b, nq, s_c // bq),
        in_specs=[
            pl.BlockSpec((b, 1), lambda b_, h, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, w, d), lambda b_, h, i: (b_, h // groups, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, w, d), lambda b_, h, i: (b_, h // groups, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=_interpret(),
    )(start32, qh, kh, vh)
    return out.transpose(0, 2, 1, 3)


def _chunk_kernel_native_q8(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                            o_ref, acc_ref, m_ref, l_ref, *, bq: int,
                            bk: int, nq: int, nkv: int, d: int,
                            scale: float):
    """int8 twin of _chunk_kernel_native: serving-layout int8 KV slabs
    ([bk, Nkv·D], half-width DMA) with [Nkv, bk] scale planes,
    dequantized in VMEM per head."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    start = pos_ref[b]
    groups = nq // nkv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk <= start + (i + 1) * bq - 1)
    def _accumulate():
        row_pos = start + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0)
        kv_k = k_ref[0]                                      # [bk, Nkv·D] i8
        kv_v = v_ref[0]
        ks = ks_ref[0]                                       # [Nkv, bk] f32
        vs = vs_ref[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        mask = col <= row_pos

        def dq(slab, scales, hk):
            return (slab[:, hk * d:(hk + 1) * d].astype(jnp.float32)
                    * scales[hk][:, None])                   # [bk, D]

        for h in range(nq):
            hk = h // groups
            qh = q_ref[0][:, h * d:(h + 1) * d].astype(jnp.float32) * scale
            s = jax.lax.dot_general(
                qh, dq(kv_k, ks, hk), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [bq, bk]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[:, h:h + 1]
            l_prev = l_ref[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[:, h:h + 1] = m_new
            l_ref[:, h:h + 1] = l_prev * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[:, h * d:(h + 1) * d] = (
                acc_ref[:, h * d:(h + 1) * d] * alpha
                + jnp.dot(p, dq(kv_v, vs, hk),
                          preferred_element_type=jnp.float32))

    @pl.when(j == nb - 1)
    def _done():
        for h in range(nq):
            o_ref[0, :, h * d:(h + 1) * d] = (
                acc_ref[:, h * d:(h + 1) * d]
                / jnp.maximum(l_ref[:, h:h + 1], 1e-30)).astype(o_ref.dtype)


def _chunk_kernel_q8(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                     acc_ref, m_ref, l_ref, *, bq: int, bk: int,
                     scale: float):
    """int8 twin of _chunk_kernel, tiled over the window like
    _decode_kernel_q8 (grid B × Nq × S_c/bq × W/bk with flash scratch):
    each step DMAs one int8 [bk, D] K/V tile plus its [bk, 1] scale
    column and dequantizes in VMEM.  Blocked scales matter: a (w, 1)
    resident plane would lane-pad ~128× in VMEM and dwarf the bytes the
    int8 halving saves at long windows."""
    b = pl.program_id(0)
    i = pl.program_id(2)
    j = pl.program_id(3)
    nb = pl.num_programs(3)
    start = pos_ref[b, 0]
    row_pos = start + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, 1), 0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # [BQ, D]
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]       # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bk
    s = jnp.where(col <= row_pos, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_chunk_attention_q8(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, k_scale: jax.Array,
                             v_scale: jax.Array,
                             q_positions: jax.Array) -> jax.Array:
    """``flash_chunk_attention`` over an int8 contiguous cache
    (TierConfig.kv_quantize): caches [B,W,Nkv,D] int8, scales [B,W,Nkv]
    f32.  Same contiguous-positions contract as the bf16 kernel; the XLA
    fallback dequantizes a full-window view instead.  Same two regimes
    as the bf16 wrapper: suffix-sized chunks run the in-place
    native-layout kernel, large chunks the wide transpose kernel."""
    b, s_c, nq, d = q.shape
    w, nkv = k_cache.shape[1], k_cache.shape[2]
    groups = nq // nkv
    bq = min(s_c, 128)
    bk = min(w, 128)
    if s_c % bq or w % bk:
        raise ValueError(
            f"flash_chunk_attention_q8: chunk {s_c} / window {w} not "
            f"multiples of the ({bq}, {bk}) blocks — use power-of-two "
            "buckets")

    if s_c <= 256:
        kf = k_cache.reshape(b, w, nkv * d)      # free: contiguous dims
        vf = v_cache.reshape(b, w, nkv * d)
        qf = q.reshape(b, s_c, nq * d)
        ks = k_scale.transpose(0, 2, 1).astype(jnp.float32)  # [B, Nkv, W]
        vs = v_scale.transpose(0, 2, 1).astype(jnp.float32)
        starts = q_positions[:, 0].astype(jnp.int32)         # [B]
        kernel = functools.partial(_chunk_kernel_native_q8, bq=bq, bk=bk,
                                   nq=nq, nkv=nkv, d=d, scale=d ** -0.5)

        def kv_index(b_, i, j, p):
            return (b_, jnp.minimum(j, (p[b_] + (i + 1) * bq - 1) // bk), 0)

        def scale_index(b_, i, j, p):
            return (b_, 0, jnp.minimum(j, (p[b_] + (i + 1) * bq - 1) // bk))

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s_c // bq, w // bk),
            in_specs=[
                pl.BlockSpec((1, bq, nq * d),
                             lambda b_, i, j, p: (b_, i, 0)),
                pl.BlockSpec((1, bk, nkv * d), kv_index),
                pl.BlockSpec((1, bk, nkv * d), kv_index),
                pl.BlockSpec((1, nkv, bk), scale_index),
                pl.BlockSpec((1, nkv, bk), scale_index),
            ],
            out_specs=pl.BlockSpec((1, bq, nq * d),
                                   lambda b_, i, j, p: (b_, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, nq * d), jnp.float32),
                pltpu.VMEM((bq, nq), jnp.float32),
                pltpu.VMEM((bq, nq), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
            interpret=_interpret(),
        )(starts, qf, kf, vf, ks, vs)
        return out.reshape(b, s_c, nq, d)

    qh = q.transpose(0, 2, 1, 3)                             # [B, Nq, S_c, D]
    kh = k_cache.transpose(0, 2, 1, 3)                       # [B, Nkv, W, D]
    vh = v_cache.transpose(0, 2, 1, 3)
    ksh = k_scale.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    vsh = v_scale.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    start32 = q_positions[:, :1].astype(jnp.int32)           # [B, 1] scalars

    kernel = functools.partial(_chunk_kernel_q8, bq=bq, bk=bk,
                               scale=d ** -0.5)
    kv_idx = lambda b_, h, i, j: (b_, h // groups, j, 0)
    out = pl.pallas_call(
        kernel,
        grid=(b, nq, s_c // bq, w // bk),
        in_specs=[
            pl.BlockSpec((b, 1), lambda b_, h, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d), kv_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d), kv_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, 1), kv_idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, 1), kv_idx, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, i, j: (b_, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(start32, qh, kh, vh, ksh, vsh)
    return out.transpose(0, 2, 1, 3)


# =============================================================================
# Paged chunk prefill: suffix queries against table blocks of the KV pool
# =============================================================================

def _paged_chunk_kernel(tbl_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, bq: int, bs: int,
                        scale: float):
    """Flash recurrence over one slot's block-table window with the
    per-query frontier of _chunk_kernel (row r attends cache cols ≤
    start + r): grid (Nq, S_c/bq, W/bs), innermost j streams pool blocks
    through VMEM via the scalar-prefetched table."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale                 # [BQ, D]
    k = k_ref[0, 0]                                          # [bs, D]
    v = v_ref[0, 0]
    row_pos = start_ref[0] + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, 1), 0)

    s = jnp.dot(q, k.T.astype(jnp.float32),
                preferred_element_type=jnp.float32)          # [BQ, bs]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
    s = jnp.where(col <= row_pos, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_chunk_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, table: jax.Array,
                          start: jax.Array, window: int) -> jax.Array:
    """Suffix-chunk attention straight out of a paged KV pool: q
    [1, S_c, Nq, D] (the chunk's queries at absolute positions start+r),
    pools [Nkv, NB, bs, D], table [MB] the slot's block row, start [1]
    -> [1, S_c, Nq, D].  ``window`` (static, multiple of bs) bounds the
    attended positions; the chunk's own K/V are already scattered into the
    table's blocks (write-before-attend), and the per-query causal
    frontier masks everything past each row.  Replaces the XLA path's
    whole-window gather in engine/paged_kv.chunk_prefill_paged."""
    _, s_c, nq, d = q.shape
    nkv, bs = k_pool.shape[0], k_pool.shape[2]
    groups = nq // nkv
    bq = min(s_c, 128)
    if s_c % bq or window % bs:
        raise ValueError(
            f"paged_chunk_attention: chunk {s_c} / window {window} not "
            f"multiples of the ({bq}, {bs}) blocks")
    wb = window // bs

    qh = q[0].transpose(1, 0, 2)                             # [Nq, S_c, D]
    tbl32 = table.astype(jnp.int32)
    start32 = start.astype(jnp.int32).reshape(1)

    kernel = functools.partial(_paged_chunk_kernel, bq=bq, bs=bs,
                               scale=d ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq, s_c // bq, wb),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda h, i, j, tbl, st: (h, i, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda h, i, j, tbl, st: (h // groups, tbl[j], 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda h, i, j, tbl, st: (h // groups, tbl[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d),
                               lambda h, i, j, tbl, st: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=_interpret(),
    )(tbl32, start32, qh, k_pool, v_pool)
    return out.transpose(1, 0, 2)[None]                      # [1, S_c, Nq, D]


# =============================================================================
# Paged decode: block-table attention straight out of the KV pool
# =============================================================================

def _paged_decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, bs: int, scale: float):
    """Flash recurrence over one slot's block table (grid: B × Nkv × MB,
    table-block index j innermost).  The pipeline DMAs pool block
    ``tables[b, j]`` into VMEM via the scalar-prefetched index map — the
    gather that the XLA path materializes in HBM never exists here."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Per-slot frontier: blocks past this slot's length are mapped by the
    # index_map onto the frontier block (the DMA dedupes on the repeated
    # index) and skipped here, so each slot pays for ITS length, not the
    # batch max.
    @pl.when(j * bs <= pos_ref[b])
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, D]
        k = k_ref[0, 0]                                      # [bs, D]
        v = v_ref[0, 0]

        s = jnp.dot(q, k.T.astype(jnp.float32),
                    preferred_element_type=jnp.float32)      # [G, bs]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        s = jnp.where(col <= pos_ref[b], s, NEG_INF)         # ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, tables: jax.Array,
                           pos: jax.Array) -> jax.Array:
    """Batched one-token decode attention over a paged KV pool
    (engine/paged_kv.py head-major layout): q [B, Nq, D], pools
    [Nkv, NB, bs, D], tables [B, MB] pool block ids, pos [B] -> [B, Nq, D].

    Logical position p of slot b lives at pool cell
    ``(h, tables[b, p // bs], p % bs)``; cells past ``pos[b]`` (and trash/
    garbage blocks the table points at beyond the allocation) are masked by
    the in-kernel ragged frontier.  Replaces the XLA path's
    ``pool[:, tables]`` gather — which materializes [B, MB·bs, Nkv, D] in
    HBM every layer of every decode step — with per-(head, block) VMEM
    streaming: each grid step DMAs exactly one [bs, D] tile."""
    b, nq, d = q.shape
    nkv, bs = k_pool.shape[0], k_pool.shape[2]
    mb = tables.shape[1]
    groups = nq // nkv

    qh = q.reshape(b, nkv, groups, d)                        # group-major
    tables32 = tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)

    kernel = functools.partial(_paged_decode_kernel, bs=bs, scale=d ** -0.5)

    def kv_index(b_, h, j, tbl, p):
        # Clamp to the slot's frontier block: overshoot iterations repeat
        # the previous index, so their DMA is elided and their compute is
        # pl.when-skipped in the kernel.
        return (h, tbl[b_, jnp.minimum(j, p[b_] // bs)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nkv, mb),
        in_specs=[
            pl.BlockSpec((1, 1, groups, d),
                         lambda b_, h, j, tbl, p: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), kv_index),
            pl.BlockSpec((1, 1, bs, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, groups, d),
                               lambda b_, h, j, tbl, p: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((groups, d), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=_interpret(),
    )(tables32, pos32, qh, k_pool, v_pool)
    return out.reshape(b, nq, d)


def _paged_decode_kernel_q8(tables_ref, pos_ref, q_ref, k_ref, v_ref,
                            ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref,
                            *, bs: int, scale: float):
    """int8 twin of _paged_decode_kernel: pool blocks arrive as int8
    [bs, D] tiles plus per-row f32 scales [bs, 1]; dequantization happens
    in VMEM after the half-width DMA — the HBM read is what shrinks."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs <= pos_ref[b])
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, D]
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]   # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
        s = jnp.where(col <= pos_ref[b], s, NEG_INF)         # ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_q8(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, k_scale: jax.Array,
                              v_scale: jax.Array, tables: jax.Array,
                              pos: jax.Array) -> jax.Array:
    """``paged_decode_attention`` over an int8 pool (engine/paged_kv.py
    kv_quantize='int8'): pools [Nkv, NB, bs, D] int8, scales
    [Nkv, NB, bs] f32.  Streams half the KV bytes of the bf16 kernel and
    never materializes the dequantized window in HBM (the XLA fallback's
    gather does)."""
    b, nq, d = q.shape
    nkv, bs = k_pool.shape[0], k_pool.shape[2]
    mb = tables.shape[1]
    groups = nq // nkv

    qh = q.reshape(b, nkv, groups, d)                        # group-major
    tables32 = tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)
    # Scales as [Nkv, NB, bs, 1]: the trailing singleton keeps Mosaic on
    # its (sublane, lane) tiling for the tiny per-row plane.
    ks = k_scale[..., None].astype(jnp.float32)
    vs = v_scale[..., None].astype(jnp.float32)

    kernel = functools.partial(_paged_decode_kernel_q8, bs=bs,
                               scale=d ** -0.5)

    def kv_index(b_, h, j, tbl, p):
        return (h, tbl[b_, jnp.minimum(j, p[b_] // bs)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nkv, mb),
        in_specs=[
            pl.BlockSpec((1, 1, groups, d),
                         lambda b_, h, j, tbl, p: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), kv_index),
            pl.BlockSpec((1, 1, bs, d), kv_index),
            pl.BlockSpec((1, 1, bs, 1), kv_index),
            pl.BlockSpec((1, 1, bs, 1), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, groups, d),
                               lambda b_, h, j, tbl, p: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((groups, d), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=_interpret(),
    )(tables32, pos32, qh, k_pool, v_pool, ks, vs)
    return out.reshape(b, nq, d)


# =============================================================================
# Decode: masked ("ragged") single-token attention over the KV cache
# =============================================================================

def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, bk: int, nkv: int, d: int, scale: float):
    """Tiled flash recurrence over the KV length (grid B × S/bk), reading
    the cache in its SERVING layout.

    KV blocks arrive as [bk, Nkv·D] slabs of the engine's own
    [B, S, Nkv, D] cache (a free reshape — the trailing dims are
    contiguous), and heads are lane-sliced inside VMEM at 128-multiple
    offsets.  The first-generation kernel instead transposed the cache
    to head-major outside the pallas_call; a pallas operand must be
    materialized in the requested layout, so every decode step paid a
    full cache copy before the kernel read it — the r3 chip A/B measured
    that kernel LOSING to XLA by ~10% at every decode shape while the
    transpose-amortized prefill kernel won 4.4×.

    Each sequence's iterations past its own length frontier are
    index-map-clamped onto the frontier block (the repeated index elides
    the DMA) and compute-skipped — so a sequence at position p streams
    ceil((p+1)/bk) blocks, not S_max."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk <= pos_ref[b])
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale             # [Nq, D]
        kv_k = k_ref[0]                                      # [bk, Nkv·D]
        kv_v = v_ref[0]
        groups = q.shape[0] // nkv

        # Per-head scores, stacked back to [Nq, bk] (row r ↔ head r//G).
        s = jnp.concatenate([
            jax.lax.dot_general(
                q[h * groups:(h + 1) * groups],
                kv_k[:, h * d:(h + 1) * d].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, bk]
            for h in range(nkv)], axis=0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bk
        s = jnp.where(col <= pos_ref[b], s, NEG_INF)         # ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[h * groups:(h + 1) * groups].astype(kv_v.dtype),
                    kv_v[:, h * d:(h + 1) * d],
                    preferred_element_type=jnp.float32)      # [G, D]
            for h in range(nkv)], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_attention(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, pos: jax.Array) -> jax.Array:
    """Drop-in for ops.attention.decode_attention (q [B,Nq,D],
    caches [B,S_max,Nkv,D], pos [B] -> [B,Nq,D]) with a KV-length-tiled
    flash recurrence: HBM traffic scales with each sequence's OWN length
    (frontier-clamped block streaming), unlike the XLA path, which reads
    the whole allocated cache every step.  Reads the cache in place —
    no head-major transpose/copy (see _decode_kernel)."""
    b, nq, d = q.shape
    s_max, nkv = k_cache.shape[1], k_cache.shape[2]
    # 256-wide KV tiles amortize grid/DMA overhead while staying small in
    # VMEM (256·Nkv·D·2B ≈ 512 KiB at Nkv=8, D=128); cache-length ladder
    # rungs (256/1024/max_seq, engine/inference.py) are all multiples.
    bk = next((t for t in (256, 128) if s_max % t == 0), s_max)

    # Free reshapes: [B,S,Nkv,D] is contiguous in (Nkv,D).
    kf = k_cache.reshape(b, s_max, nkv * d)
    vf = v_cache.reshape(b, s_max, nkv * d)
    pos32 = pos.astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, bk=bk, nkv=nkv, d=d,
                               scale=d ** -0.5)

    def kv_index(b_, j, p):
        # Clamp past-frontier iterations onto the frontier block: the
        # repeated index skips the DMA, pl.when skips the compute.
        return (b_, jnp.minimum(j, p[b_] // bk), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s_max // bk),
        in_specs=[
            pl.BlockSpec((1, nq, d), lambda b_, j, p: (b_, 0, 0)),
            pl.BlockSpec((1, bk, nkv * d), kv_index),
            pl.BlockSpec((1, bk, nkv * d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, nq, d), lambda b_, j, p: (b_, 0, 0)),
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
    )(pos32, q, kf, vf)


def _decode_kernel_q8(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, bk: int, nkv: int, d: int,
                      scale: float):
    """int8 twin of _decode_kernel: KV slabs arrive int8 in the serving
    layout ([bk, Nkv·D], half-width DMA) with per-(row, head) f32 scales
    as [Nkv, bk] planes; dequantization happens in VMEM."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk <= pos_ref[b])
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale             # [Nq, D]
        kv_k = k_ref[0]                                      # [bk, Nkv·D] i8
        kv_v = v_ref[0]
        ks = ks_ref[0]                                       # [Nkv, bk] f32
        vs = vs_ref[0]
        groups = q.shape[0] // nkv

        def dq(slab, scales, h):
            return (slab[:, h * d:(h + 1) * d].astype(jnp.float32)
                    * scales[h][:, None])                    # [bk, D]

        s = jnp.concatenate([
            jax.lax.dot_general(
                q[h * groups:(h + 1) * groups], dq(kv_k, ks, h),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, bk]
            for h in range(nkv)], axis=0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bk
        s = jnp.where(col <= pos_ref[b], s, NEG_INF)         # ragged mask

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[h * groups:(h + 1) * groups], dq(kv_v, vs, h),
                    preferred_element_type=jnp.float32)      # [G, D]
            for h in range(nkv)], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_attention_q8(q: jax.Array, k_cache: jax.Array,
                              v_cache: jax.Array, k_scale: jax.Array,
                              v_scale: jax.Array,
                              pos: jax.Array) -> jax.Array:
    """``flash_decode_attention`` over an int8 contiguous cache
    (TierConfig.kv_quantize): caches [B,S_max,Nkv,D] int8, scales
    [B,S_max,Nkv] f32.  Streams half the KV bytes of the bf16 kernel
    with the same frontier-clamped tiling and the same in-place cache
    reads (only the TINY scale planes are transposed — S·Nkv·4 B, vs
    the S·Nkv·D·2 B cache copy the first-generation kernel paid); the
    XLA fallback dequantizes a gathered view instead."""
    b, nq, d = q.shape
    s_max, nkv = k_cache.shape[1], k_cache.shape[2]
    bk = next((t for t in (256, 128) if s_max % t == 0), s_max)

    kf = k_cache.reshape(b, s_max, nkv * d)      # free: contiguous dims
    vf = v_cache.reshape(b, s_max, nkv * d)
    # Scales to [B, Nkv, S]: (Nkv, bk) blocks tile cleanly (f32 sublane
    # = 8 = typical Nkv); per-head rows broadcast over D in-kernel.
    ks = k_scale.transpose(0, 2, 1).astype(jnp.float32)
    vs = v_scale.transpose(0, 2, 1).astype(jnp.float32)
    pos32 = pos.astype(jnp.int32)

    kernel = functools.partial(_decode_kernel_q8, bk=bk, nkv=nkv, d=d,
                               scale=d ** -0.5)

    def kv_index(b_, j, p):
        return (b_, jnp.minimum(j, p[b_] // bk), 0)

    def scale_index(b_, j, p):
        return (b_, 0, jnp.minimum(j, p[b_] // bk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s_max // bk),
        in_specs=[
            pl.BlockSpec((1, nq, d), lambda b_, j, p: (b_, 0, 0)),
            pl.BlockSpec((1, bk, nkv * d), kv_index),
            pl.BlockSpec((1, bk, nkv * d), kv_index),
            pl.BlockSpec((1, nkv, bk), scale_index),
            pl.BlockSpec((1, nkv, bk), scale_index),
        ],
        out_specs=pl.BlockSpec((1, nq, d), lambda b_, j, p: (b_, 0, 0)),
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
    )(pos32, q, kf, vf, ks, vs)
