"""Attention ops for prefill and single-step decode.

Pure-XLA implementations (einsum + softmax) that GSPMD can shard over a 'tp'
mesh axis (heads dimension), and the dispatching wrappers the engines call.
ONE implementation stands behind each wrapper, chosen from what the code
sees when it traces (``resolve_impl`` and static shapes):

``causal``         the Pallas flash prefill (``pallas_attention.py``) where
                   the engine opted into kernels, else ``causal_attention``;
``decode``/``chunk``  (contiguous cache) the XLA form, an int8 cache
                   dequantized first;
``paged_decode``   the served tick: ``decode_form`` says ``streamed`` (the
                   kernel of ``rows_attention.py``) or ``merged`` (XLA);
``ragged_verify``/``paged_chunk``  the table gather and ``chunk_attention``.

Shapes follow the KV-cache layout [B, S, N_kv, D] (batch, sequence, kv-heads,
head_dim); queries are [B, S, N_q, D] with N_q a multiple of N_kv (GQA).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def resolve_impl(impl: str = "auto") -> str:
    """Resolve the attention implementation choice.

    'auto' resolves to the portable XLA path: it is GSPMD-partitionable, so
    it is the only safe default inside pjit-sharded computations (the
    trainer's sp/tp meshes, tensor-sharded tiers).  'pallas' is an explicit
    opt-in used by unsharded serving engines (engine/inference.py picks it
    for single-device tiers on TPU) and means the flash prefill and the
    streamed rows kernel where they serve; a pallas_call has no GSPMD
    sharding rule, so opting in under a >1-device mesh would replicate the
    operands.  DLLM_ATTENTION=xla|pallas overrides everything (kill switch
    / forced testing); any other value raises rather than failing open.
    """
    env = os.environ.get("DLLM_ATTENTION")
    if env is not None:
        if env not in ("xla", "pallas"):
            raise ValueError(f"DLLM_ATTENTION={env!r}: expected 'xla' or 'pallas'")
        return env
    if impl == "auto":
        return "xla"
    if impl not in ("xla", "pallas"):
        raise ValueError(f"attention impl {impl!r}: expected 'auto', 'xla' "
                         "or 'pallas'")
    return impl


def causal(q: jax.Array, k: jax.Array, v: jax.Array,
           impl: str = "auto") -> jax.Array:
    """Dispatching causal attention (prefill)."""
    if resolve_impl(impl) == "pallas":
        from .pallas_attention import flash_causal_attention
        return flash_causal_attention(q, k, v)
    return causal_attention(q, k, v)


def _dequant_cache(k_cache, v_cache, k_scale, v_scale, dtype):
    """Contiguous int8 cache ([.., S, Nkv, D] + [.., S, Nkv] scales) ->
    model-dtype views for the XLA attention math (the cast fuses into the
    attention einsum read; the HBM-resident cache stays int8)."""
    if k_scale is None:
        return k_cache, v_cache
    from .quant import dequantize_kv_rows
    return (dequantize_kv_rows(k_cache, k_scale, dtype),
            dequantize_kv_rows(v_cache, v_scale, dtype))


def decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
           pos: jax.Array, k_scale: jax.Array = None,
           v_scale: jax.Array = None) -> jax.Array:
    """Single-step decode attention over a contiguous cache.
    ``k_scale``/``v_scale`` mark an int8 cache (TierConfig.kv_quantize),
    dequantized as a view."""
    return decode_attention(
        q, *_dequant_cache(k_cache, v_cache, k_scale, v_scale, q.dtype), pos)


def chunk(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
          q_positions: jax.Array, k_scale: jax.Array = None,
          v_scale: jax.Array = None) -> jax.Array:
    """Chunked-prefill attention (suffix queries vs the contiguous cache
    window); scales as in ``decode``."""
    return chunk_attention(
        q, *_dequant_cache(k_cache, v_cache, k_scale, v_scale, q.dtype),
        q_positions)


def _gather_pool_seq(q, k_pool, v_pool, tables, k_scale, v_scale,
                     layer=None, merged=False):
    """The paged ops' ONE table gather: pools + tables [B, MB] ->
    contiguous [B, S, Nkv, D] views in ``q``'s dtype (int8 pools
    dequantized through the gathered scales).  Shared by the decode
    (q_len=1), verify (q_len=γ+1) and chunk ops so their byte-parity is
    mechanical, not maintained by hand.

    The pools are per-layer head-major views ``[Nkv, NB, bs(, D)]`` (a tp
    hook's shard) or, with a ``layer`` index, the WHOLE token-major pool
    ``[L, NB, bs, Nkv * D]`` (scales ``[L, NB, bs, Nkv]``), gathered at
    (layer, block) directly: whole blocks of whole tokens, already in the
    order the attention wants, and no layer-sized slice in between.

    ``merged`` (token-major pool only) leaves the gathered rows as they
    rest, ``[B, S, Nkv * D]`` with the heads side by side on the lanes,
    an int8 pool's scale repeated over its head's ``D`` columns: what
    ``merged_decode_attention`` contracts over.  Splitting the head axis
    off a window-sized array is a copy on a TPU wherever ``D`` is not a
    whole number of 128-lane rows (at 64 it is padded to twice its
    size); the chunk and verify ops still pay it, because their queries
    are long and the merged form's zeros would be real work."""
    b, mb = tables.shape
    d = q.shape[-1]
    if layer is None:
        def seq(pool, *_):     # [Nkv, B, MB, bs(, D)] -> [B, S, Nkv(, D)]
            blocks = jnp.moveaxis(pool[:, tables], 0, 3)
            return blocks.reshape(b, -1, *blocks.shape[3:])
    else:
        def seq(pool, *heads):  # [B, MB, bs, ·] -> [B, S, Nkv(, D)]
            return pool[layer, tables].reshape(b, mb * pool.shape[2], -1,
                                               *heads)

    def spread(scale):          # [B, S, Nkv] -> over the rows' columns
        return jnp.repeat(scale, d, axis=-1) if merged else scale[..., None]

    heads = () if merged else (d,)
    with jax.named_scope("kv_gather"):
        k_seq, v_seq = seq(k_pool, *heads), seq(v_pool, *heads)
        if k_scale is not None:
            k_seq = (k_seq.astype(jnp.float32)
                     * spread(seq(k_scale))).astype(q.dtype)
            v_seq = (v_seq.astype(jnp.float32)
                     * spread(seq(v_scale))).astype(q.dtype)
    return k_seq, v_seq


def decode_form(impl: str, n_q: int, head_dim: int, table_blocks: int,
                block_size: int, row: int, dtype) -> str:
    """The form ``paged_decode`` over the WHOLE token-major pool
    (``layer=i``: the served tick) attends in, from what the code sees
    when it traces: the one rule the op dispatches by and
    ``engine.decode_attention_form`` labels by.

    ``streamed``  an engine that opted into kernels (``impl`` resolves to
                  'pallas': unsharded, on the TPU) and a window
                  ``ops.rows_attention`` serves (static shapes: floating
                  rows of whole lane-widths, a K/V head to every query
                  head): the block table walked in the kernel, the pool
                  read once where it rests;
    ``merged``    everything else (a mesh's GSPMD path, the CPU, an int8
                  pool, GQA's narrow rows, a row off the lanes): the XLA
                  gather and ``merged_decode_attention``."""
    from . import rows_attention
    if resolve_impl(impl) == "pallas" and rows_attention.serves(
            n_q, head_dim, table_blocks, block_size, row, dtype):
        return "streamed"
    return "merged"


def paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                 tables: jax.Array, pos: jax.Array,
                 impl: str = "auto", k_scale: jax.Array = None,
                 v_scale: jax.Array = None, layer=None) -> jax.Array:
    """Batched one-token decode attention over a paged KV pool
    (engine/paged_kv.py): q [B, Nq, D], tables [B, MB], pos [B] -> [B, Nq,
    D], each slot over positions ``<= pos[b]`` of its table's window.  The
    windowed tick passes a truncated table, the fused tick every slot's
    FULL row: one op, and what is gathered is what the tables span.

    ``layer`` (here and in the two ops below): the pools and scales are
    the WHOLE token-major arrays of engine/paged_kv.py ([L, NB, bs,
    Nkv * D], scales [L, NB, bs, Nkv], int8 rows where scales are given)
    and this is the traced layer to attend, in the form ``decode_form``
    names: ``streamed`` through the table where the pool rests, or its
    rows gathered ``[B, S, Nkv * D]`` for ``merged_decode_attention``.
    Without it they are a layer's head-major views ``[Nkv, NB, bs(, D)]``
    (a tp hook's shard), gathered to ``[B, S, Nkv, D]`` for
    ``decode_attention``.  Only this op (query length 1) has a merged
    form; ``ragged_verify`` and ``paged_chunk`` split the head axis off
    their window whichever they are handed."""
    if layer is None:
        return decode_attention(
            q, *_gather_pool_seq(q, k_pool, v_pool, tables, k_scale,
                                 v_scale), pos)
    if decode_form(impl, *q.shape[1:], tables.shape[1], *k_pool.shape[2:],
                   k_pool.dtype) == "streamed":
        from .rows_attention import paged_rows_decode_attention
        return paged_rows_decode_attention(q, k_pool, v_pool, tables, pos,
                                           layer)
    return merged_decode_attention(
        q, *_gather_pool_seq(q, k_pool, v_pool, tables, k_scale, v_scale,
                             layer, merged=True), pos)


def ragged_verify(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                  tables: jax.Array, pos: jax.Array,
                  k_scale: jax.Array = None, v_scale: jax.Array = None,
                  layer=None) -> jax.Array:
    """Speculative-verify attention over a paged KV pool: q [B, G, Nq, D],
    G = γ+1 chunk queries per slot at absolute positions ``pos[b] + g``
    (``pos`` [B] is the FIRST query's position; the chunk's K/V are
    already written, write-before-attend), tables [B, MB] each slot's FULL
    row -> [B, G, Nq, D].  The SAME ``_gather_pool_seq`` gather as
    ``paged_decode`` (so the q_len=1 and q_len=γ+1 ops agree
    block-for-block by construction), attended through ``chunk_attention``
    with per-query positions."""
    k_seq, v_seq = _gather_pool_seq(q, k_pool, v_pool, tables,
                                    k_scale, v_scale, layer)
    q_pos = pos[:, None] + jnp.arange(q.shape[1])[None]      # [B, G]
    return chunk_attention(q, k_seq, v_seq, q_pos)


def paged_chunk(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                table: jax.Array, q_pos: jax.Array, window: int,
                k_scale: jax.Array = None, v_scale: jax.Array = None,
                layer=None) -> jax.Array:
    """Suffix-chunk attention over a paged KV pool
    (engine/paged_kv.chunk_prefill_paged): q [1, S_c, Nq, D], table [MB],
    q_pos [1, S_c] clamped absolute positions, static ``window``: the
    table's first ``window`` positions gathered and masked by ``q_pos``."""
    k_seq, v_seq = _gather_pool_seq(
        q, k_pool, v_pool, table[None, :window // k_pool.shape[-2]],
        k_scale, v_scale, layer)
    return chunk_attention(q, k_seq, v_seq, q_pos)


def _expand_kv(x: jax.Array, groups: int) -> jax.Array:
    """[B, S, N_kv, D] -> [B, S, N_kv*groups, D] by repeating each kv head."""
    if groups == 1:
        return x
    b, s, n_kv, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, n_kv, groups, d)
    ).reshape(b, s, n_kv * groups, d)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Full-sequence causal attention (prefill).

    q: [B, S, N_q, D], k/v: [B, S, N_kv, D] -> [B, S, N_q, D].
    Softmax accumulates in float32 regardless of input dtype.
    """
    groups = q.shape[2] // k.shape[2]
    k = _expand_kv(k, groups)
    v = _expand_kv(v, groups)

    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32) * scale

    s = q.shape[1]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    logits = jnp.where(causal[None, None], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def chunk_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    q_positions: jax.Array,
) -> jax.Array:
    """Chunked-prefill attention: a chunk of new queries against the full
    KV cache (prefix + the chunk itself, already written).

    This is the op behind session KV prefix reuse and chunked prefill: only
    the suffix of a prompt is run as queries, attending causally to the
    cached prefix at absolute positions.  Generalizes ``decode_attention``
    (chunk of 1) and ``causal_attention`` (chunk = whole sequence, empty
    prefix).

    q: [B, S_c, N_q, D] (the chunk's queries, RoPE already applied at
       absolute positions)
    k_cache/v_cache: [B, S_max, N_kv, D] with positions < start holding the
       prefix and [start, start+S_c) holding the chunk's own K/V
    q_positions: [B, S_c] absolute position of each query token; cache
       indices > position are masked (slots not yet valid for that query).
       Right-padding is harmless: padded queries produce garbage rows that
       the caller never reads.
    Returns [B, S_c, N_q, D].
    """
    groups = q.shape[2] // k_cache.shape[2]
    k = _expand_kv(k_cache, groups)
    v = _expand_kv(v_cache, groups)

    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32) * scale

    s_max = k.shape[1]
    valid = jnp.arange(s_max)[None, None, :] <= q_positions[:, :, None]
    logits = jnp.where(valid[:, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """One-token decode attention against the full KV cache.

    q: [B, N_q, D] (the single new query position per sequence)
    k_cache/v_cache: [B, S_max, N_kv, D]
    pos: [B] current position of the query token (0-based); keys at indices
         > pos are masked (cache slots not yet written).
    Returns [B, N_q, D].
    """
    groups = q.shape[1] // k_cache.shape[2]
    k = _expand_kv(k_cache, groups)
    v = _expand_kv(v_cache, groups)

    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bnd,bknd->bnk", q, k).astype(jnp.float32) * scale

    s_max = k.shape[1]
    valid = jnp.arange(s_max)[None, :] <= pos[:, None]          # [B, S_max]
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bnk,bknd->bnd", probs, v)


def merged_decode_attention(
    q: jax.Array,
    k_rows: jax.Array,
    v_rows: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """``decode_attention`` over rows whose heads are MERGED on the minor
    axis, as the token-major pool of engine/paged_kv.py keeps them.

    q: [B, N_q, D]; k_rows/v_rows: [B, S, N_kv * D]; pos: [B] as in
    ``decode_attention``.  Returns [B, N_q, D].

    Both products contract against the rows as they rest, so no head
    axis is split off a window-sized array: the query is spread
    block-diagonally (row ``n`` holds ``q[b, n]`` at its kv head's ``D``
    columns, zeros elsewhere), ``scores = Q · K_rows^T`` and ``full = p ·
    V_rows`` accumulate in float32, and each query head's own ``D``
    columns are taken from its row of ``full``.  The zeros add exactly
    nothing, so this is ``decode_attention``'s mathematics at ``N_kv``
    times its multiplications — on a step bound by reading the window,
    for a query of ONE token; a chunk's or a verify step's queries are
    many, and there the waste would be real (``chunk_attention`` keeps
    the head split).
    """
    b, n_q, d = q.shape
    n_kv = k_rows.shape[-1] // d
    # own[n, j]: kv head j serves query head n.
    own = (jnp.arange(n_q)[:, None] // (n_q // n_kv)
           == jnp.arange(n_kv)[None, :])[None, :, :, None]
    q_rows = jnp.where(own, q[:, :, None, :], 0).reshape(b, n_q, n_kv * d)

    scale = d ** -0.5
    logits = jnp.einsum("bnc,bkc->bnk", q_rows, k_rows,
                        preferred_element_type=jnp.float32) * scale

    valid = jnp.arange(k_rows.shape[1])[None, :] <= pos[:, None]  # [B, S]
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(v_rows.dtype)
    full = jnp.einsum("bnk,bkc->bnc", probs, v_rows,
                      preferred_element_type=jnp.float32)
    out = jnp.where(own, full.reshape(b, n_q, n_kv, d), 0).sum(axis=2)
    return out.astype(v_rows.dtype)
