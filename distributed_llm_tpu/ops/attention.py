"""Attention ops for prefill and single-step decode.

Pure-XLA implementations (einsum + softmax) that GSPMD can shard over a 'tp'
mesh axis (heads dimension).  The Pallas flash-attention kernel in
``pallas_attention.py`` replaces the prefill path on TPU when enabled; these
remain the portable fallback and the reference semantics.

Shapes follow the KV-cache layout [B, S, N_kv, D] (batch, sequence, kv-heads,
head_dim); queries are [B, S, N_q, D] with N_q a multiple of N_kv (GQA).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

NEG_INF = -1e30

# Measured per-kernel dispatch table, written by
# ``python -m distributed_llm_tpu.bench.ab_kernels micro --write-dispatch``
# on real hardware and by nothing else:
# {"decode": {"default": "pallas", "2048": "xla"}, ...}.
# Consulted only when an engine opted into the Pallas family ('pallas'
# resolved, no DLLM_ATTENTION override): a kernel kind/length the A/B
# showed losing is demoted back to XLA per shape, instead of the round-1
# blanket env pin.
_DISPATCH_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "..", "bench", "ab_dispatch.json")
_DISPATCH_TABLE: Optional[dict] = None
_DISPATCH_META: Optional[dict] = None

# The registry of dispatch kinds: every kind ``_choose`` is consulted
# with by the wrappers below.  This is the contract surface between the
# serving ops and the measured table — bench/ab_kernels.py derives its
# measurable case classes (ALL_KINDS) from it, and
# tests/test_kernel_dispatch.py asserts the committed ab_dispatch.json
# covers every entry, so a new kernel kind cannot ship without a table
# row (the table had once silently fallen behind the kernels).
DISPATCH_KINDS = ("prefill", "decode", "decode_q8", "chunk", "chunk_q8",
                  "paged_decode", "paged_decode_q8", "paged_chunk",
                  "ragged_decode", "ragged_decode_q8",
                  "ragged_verify", "ragged_verify_q8")


def _load_dispatch() -> None:
    """Load (once) the measured dispatch table + its provenance.  A table
    whose ``kernel_gen`` is absent or behind the current Pallas kernels
    still dispatches — re-measuring needs hardware — but the staleness is
    logged and surfaced via ``dispatch_provenance`` (/stats), so old
    hardware conclusions read as provisional, not authoritative."""
    global _DISPATCH_TABLE, _DISPATCH_META
    if _DISPATCH_TABLE is not None:
        return
    from .pallas_attention import KERNEL_GEN
    meta = {"path": _DISPATCH_PATH, "current_kernel_gen": KERNEL_GEN,
            "backend": None, "kernel_gen": None, "active": False,
            "stale_kernel_gen": False}
    try:
        with open(_DISPATCH_PATH) as f:
            data = json.load(f)
        meta["backend"] = data.get("backend")
        meta["kernel_gen"] = data.get("kernel_gen")
        # A table measured on another backend is meaningless here
        # (interpreter-mode CPU timings would wrongly demote every
        # kernel on TPU): ignore it.
        if data.get("backend") == jax.default_backend():
            _DISPATCH_TABLE = data.get("dispatch", {})
            meta["active"] = bool(_DISPATCH_TABLE)
            if meta["active"] and meta["kernel_gen"] != KERNEL_GEN:
                meta["stale_kernel_gen"] = True
                logger.warning(
                    "dispatch table %s was measured at kernel_gen=%s but "
                    "the kernels are at gen %s — its verdicts are "
                    "provisional until re-measured on hardware "
                    "(bench.ab_kernels micro --write-dispatch)",
                    _DISPATCH_PATH, meta["kernel_gen"], KERNEL_GEN)
        else:
            _DISPATCH_TABLE = {}
    except (OSError, ValueError):
        _DISPATCH_TABLE = {}
    _DISPATCH_META = meta


def dispatch_provenance() -> dict:
    """Provenance of the measured kernel-dispatch table: backend +
    kernel generation it was measured on, whether it is steering this
    process, and whether it is stale w.r.t. the current kernels."""
    _load_dispatch()
    if _DISPATCH_META is None:
        # Table injected directly (tests monkeypatch _DISPATCH_TABLE
        # without meta): report activity, claim nothing about origin.
        from .pallas_attention import KERNEL_GEN
        return {"path": _DISPATCH_PATH, "current_kernel_gen": KERNEL_GEN,
                "backend": None, "kernel_gen": None,
                "active": bool(_DISPATCH_TABLE),
                "stale_kernel_gen": False}
    return dict(_DISPATCH_META)


def _measured_impl(kind: str, length: Optional[int]) -> Optional[str]:
    _load_dispatch()
    entry = _DISPATCH_TABLE.get(kind)
    if isinstance(entry, str):
        return entry
    if isinstance(entry, dict):
        hit = entry.get(str(length))
        if hit is None and length is not None:
            # Off-ladder shape (e.g. the batched engine's trimmed paged
            # window, which takes many values): snap to the nearest
            # measured rung so demotions cover it.
            rungs = [int(k) for k in entry if str(k).isdigit()]
            if rungs:
                hit = entry[str(min(rungs,
                                    key=lambda r: abs(r - int(length))))]
        if hit is None:
            hit = entry.get("default")
        return hit
    return None


def _choose(impl: str, kind: str, length: Optional[int]) -> str:
    resolved = resolve_impl(impl)
    if resolved == "pallas" and os.environ.get("DLLM_ATTENTION") is None:
        measured = _measured_impl(kind, length)
        if measured in ("xla", "pallas"):
            return measured
    return resolved


def decode_kv_span(kind: str, length: int, positions, impl: str = "auto",
                   block: Optional[int] = None) -> float:
    """Average per-sequence KV span the ACTIVE decode kernel streams per
    step, for roofline accounting (utils/roofline.py decode_work kv_ctx).

    The XLA paths read the full allocated span; the Pallas decode kernels
    clamp their grid onto the causal frontier and stream only
    ceil((pos+1)/block) tiles (pallas_attention.py ``_decode_kernel`` /
    paged index maps), so charging the allocated span would overstate
    hbm_util — the judged decode metric — past 1.0.

    ``positions`` iterates the 0-based query positions of the accounted
    steps (per step for a single sequence, per row for a batched tick);
    ``block`` is the paged pool's block size, or None for the contiguous
    kernels' own tile ladder."""
    if _choose(impl, kind, length) != "pallas":
        return float(length)
    if block is None:      # flash_decode_* tile ladder (pallas_attention.py)
        block = next((t for t in (256, 128) if length % t == 0), length)
    spans = [min(length, (int(p) // block + 1) * block) for p in positions]
    return float(sum(spans)) / max(len(spans), 1)


def resolve_impl(impl: str = "auto") -> str:
    """Resolve the attention implementation choice.

    'auto' resolves to the portable XLA path: it is GSPMD-partitionable, so
    it is the only safe default inside pjit-sharded computations (the
    trainer's sp/tp meshes, tensor-sharded tiers).  'pallas' is an explicit
    opt-in used by unsharded serving engines (engine/inference.py picks it
    for single-device tiers on TPU); a pallas_call has no GSPMD sharding
    rule, so opting in under a >1-device mesh would replicate the operands.
    DLLM_ATTENTION=xla|pallas overrides everything (kill switch / forced
    testing); any other value raises rather than failing open.
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
    if _choose(impl, "prefill", q.shape[1]) == "pallas":
        from .pallas_attention import flash_causal_attention
        return flash_causal_attention(q, k, v)
    return causal_attention(q, k, v)


def _dequant_cache(k_cache, v_cache, k_scale, v_scale, dtype):
    """Contiguous int8 cache ([.., S, Nkv, D] + [.., S, Nkv] scales) ->
    model-dtype views for the XLA attention math (the cast fuses into the
    attention einsum read; the HBM-resident cache stays int8)."""
    from .quant import dequantize_kv_rows
    return (dequantize_kv_rows(k_cache, k_scale, dtype),
            dequantize_kv_rows(v_cache, v_scale, dtype))


def decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
           pos: jax.Array, impl: str = "auto", k_scale: jax.Array = None,
           v_scale: jax.Array = None) -> jax.Array:
    """Dispatching single-step decode attention.  ``k_scale``/``v_scale``
    mark an int8 contiguous cache (TierConfig.kv_quantize): the Pallas
    path streams int8 tiles + scales with in-VMEM dequant (its own
    'decode_q8' dispatch kind); the XLA path dequantizes a view."""
    if k_scale is not None:
        if _choose(impl, "decode_q8", k_cache.shape[1]) == "pallas":
            from .pallas_attention import flash_decode_attention_q8
            return flash_decode_attention_q8(q, k_cache, v_cache, k_scale,
                                             v_scale, pos)
        k_cache, v_cache = _dequant_cache(k_cache, v_cache, k_scale,
                                          v_scale, q.dtype)
        return decode_attention(q, k_cache, v_cache, pos)
    if _choose(impl, "decode", k_cache.shape[1]) == "pallas":
        from .pallas_attention import flash_decode_attention
        return flash_decode_attention(q, k_cache, v_cache, pos)
    return decode_attention(q, k_cache, v_cache, pos)


def chunk(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
          q_positions: jax.Array, impl: str = "auto",
          k_scale: jax.Array = None,
          v_scale: jax.Array = None) -> jax.Array:
    """Dispatching chunked-prefill attention (suffix queries vs the cache
    window).  The Pallas path keeps cold prefill and prefix-reuse hits on
    the same kernel family on TPU (flash recurrence, per-query frontier);
    the XLA path is the portable/shardable fallback — and the only path
    for int8 caches (scales given)."""
    # Sublane-unaligned chunk rows (e.g. the speculative verify's γ+1=5)
    # would hand Mosaic a block shape no hardware run has validated — the
    # micro A/B measures the chunk kinds at bucket-sized rows only.  Keep
    # those on XLA until a measured table covers them.
    aligned = q.shape[1] % 8 == 0
    if k_scale is not None:
        if (aligned
                and _choose(impl, "chunk_q8", k_cache.shape[1]) == "pallas"):
            from .pallas_attention import flash_chunk_attention_q8
            return flash_chunk_attention_q8(q, k_cache, v_cache, k_scale,
                                            v_scale, q_positions)
        k_cache, v_cache = _dequant_cache(k_cache, v_cache, k_scale,
                                          v_scale, q.dtype)
        return chunk_attention(q, k_cache, v_cache, q_positions)
    if aligned and _choose(impl, "chunk", k_cache.shape[1]) == "pallas":
        from .pallas_attention import flash_chunk_attention
        return flash_chunk_attention(q, k_cache, v_cache, q_positions)
    return chunk_attention(q, k_cache, v_cache, q_positions)


def _layer_views(layer, head_dim, k_pool, v_pool, k_scale=None,
                 v_scale=None):
    """Per-layer head-major ``(k, v, ks, vs)`` views ``[Nkv, NB, bs(,
    D)]`` for the kernels and hooks: with a ``layer`` index the pools are
    the WHOLE token-major arrays of engine/paged_kv.py (``[L, NB, bs,
    Nkv * D]``, scales ``[L, NB, bs, Nkv]``) and the layer is sliced out
    and turned here — a layer-sized copy, which the XLA paths below avoid
    by gathering from the whole pool; without one they already are the
    views."""
    if layer is None:
        return k_pool, v_pool, k_scale, v_scale

    def view(pool, *heads):
        x = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
        return jnp.moveaxis(x.reshape(*x.shape[:2], -1, *heads), 2, 0)

    return (view(k_pool, head_dim), view(v_pool, head_dim),
            k_scale if k_scale is None else view(k_scale),
            v_scale if v_scale is None else view(v_scale))


def _gather_pool_seq(q, k_pool, v_pool, tables, k_scale, v_scale,
                     layer=None, merged=False):
    """The paged fallbacks' ONE table gather: pools + tables [B, MB] ->
    contiguous [B, S, Nkv, D] views in ``q``'s dtype (int8 pools
    dequantized through the gathered scales).  Shared by the decode
    (q_len=1), verify (q_len=γ+1) and chunk fallbacks so their
    byte-parity is mechanical, not maintained by hand.

    The pools are per-layer head-major views ``[Nkv, NB, bs(, D)]`` or,
    with a ``layer`` index, the WHOLE token-major pool ``[L, NB, bs,
    Nkv * D]`` (scales ``[L, NB, bs, Nkv]``), gathered at (layer, block)
    directly: whole blocks of whole tokens, already in the order the
    attention wants, and no layer-sized slice in between.

    ``merged`` (token-major pool only) leaves the gathered rows as they
    rest, ``[B, S, Nkv * D]`` with the heads side by side on the lanes,
    an int8 pool's scale repeated over its head's ``D`` columns: what
    ``merged_decode_attention`` contracts over.  Splitting the head axis
    off a window-sized array is a copy on a TPU wherever ``D`` is not a
    whole number of 128-lane rows (at 64 it is padded to twice its
    size); the chunk and verify fallbacks still pay it, because their
    queries are long and the merged form's zeros would be real work."""
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


def decode_form(impl: str, kind: str, n_q: int, head_dim: int,
                table_blocks: int, block_size: int, row: int, dtype) -> str:
    """The form a decode op over the WHOLE token-major pool (``layer=i``:
    the served tick) attends in, from what the code sees when it traces —
    the one rule ``paged_decode``/``ragged_decode`` dispatch by and
    ``engine.decode_attention_form`` labels by:

    ``split``     the dispatch table (or ``DLLM_ATTENTION=pallas``) puts
                  ``kind`` on its head-major Pallas kernel, which gets a
                  layer's view (``_layer_views``: a layer-sized copy);
    ``streamed``  an engine that opted into kernels (``impl`` resolves to
                  'pallas': unsharded, on the TPU) and a window
                  ``ops.rows_attention`` serves (static shapes: floating
                  rows of whole lane-widths, a K/V head to every query
                  head): the block table walked in the kernel, the pool
                  read once where it rests;
    ``merged``    everything else — a mesh's GSPMD path, the CPU, an int8
                  pool, GQA's narrow rows, a row off the lanes: the XLA
                  gather and ``merged_decode_attention``."""
    from . import rows_attention
    if _choose(impl, kind, table_blocks * block_size) == "pallas":
        return "split"
    if (resolve_impl(impl) == "pallas" and not kind.endswith("_q8")
            and rows_attention.serves(n_q, head_dim, table_blocks,
                                      block_size, row, dtype)):
        return "streamed"
    return "merged"


def _decode_paged_fallback(q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                           layer=None, impl: str = "auto", kind=None):
    """What ``paged_decode`` and ``ragged_decode`` run where the dispatch
    table does not put them on a head-major kernel: one code path, so the
    two kinds agree byte for byte.  The form follows the representation
    it is handed: per-layer head-major views (``layer`` None: a hook's
    shard, a kernel's parity test) gather to ``[B, S, Nkv, D]`` and reuse
    ``decode_attention``, the parity reference for the Pallas kernels;
    the WHOLE token-major pool (``layer=i``: the served tick) is attended
    merged, at every ``head_dim`` — ``streamed`` through the table where
    it rests or, where ``decode_form`` says so, gathered to rows ``[B, S,
    Nkv * D]`` for ``merged_decode_attention``."""
    if layer is not None and kind is not None and decode_form(
            impl, kind, *q.shape[1:], tables.shape[1], *k_pool.shape[2:],
            k_pool.dtype) == "streamed":
        from .rows_attention import paged_rows_decode_attention
        return paged_rows_decode_attention(q, k_pool, v_pool, tables, pos,
                                           layer)
    k_seq, v_seq = _gather_pool_seq(q, k_pool, v_pool, tables,
                                    k_scale, v_scale, layer,
                                    merged=layer is not None)
    if layer is not None:
        return merged_decode_attention(q, k_seq, v_seq, pos)
    return decode_attention(q, k_seq, v_seq, pos)


def paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                 tables: jax.Array, pos: jax.Array,
                 impl: str = "auto", k_scale: jax.Array = None,
                 v_scale: jax.Array = None, layer=None) -> jax.Array:
    """Dispatching batched decode attention over a paged KV pool
    (engine/paged_kv.py): q [B, Nq, D], pools [Nkv, NB, bs, D], tables
    [B, MB], pos [B] -> [B, Nq, D].  The Pallas path walks the block table
    in-kernel; the XLA path gathers the table into a contiguous view and
    attends it (portable / GSPMD-shardable fallback).

    ``k_scale``/``v_scale`` ([Nkv, NB, bs]) mark an int8 pool: the Pallas
    path streams int8 blocks + scales and dequantizes in VMEM
    (paged_decode_attention_q8, its own dispatch kind); the XLA path
    gathers HALF the bytes and dequantizes after.

    ``layer`` (here and in the three ops below): the pools and scales
    are the WHOLE token-major arrays of engine/paged_kv.py ([L, NB, bs,
    Nkv * D], scales [L, NB, bs, Nkv]) and this is the traced layer to
    attend — the XLA path gathers straight from the whole pool, a kernel
    gets the layer's head-major view (``_layer_views``).  Which form the
    XLA path attends in follows from which of the two it was handed
    (``_decode_paged_fallback``): head-major views split by head through
    ``decode_attention``, the whole pool's rows merged through
    ``merged_decode_attention``.  Only the two decode ops (query length
    1) have a merged form; ``ragged_verify`` and ``paged_chunk`` split
    the head axis off their window whichever they are handed."""
    b, mb = tables.shape
    bs = k_pool.shape[-2]
    if k_scale is None:
        if _choose(impl, "paged_decode", mb * bs) == "pallas":
            from .pallas_attention import paged_decode_attention
            return paged_decode_attention(
                q, *_layer_views(layer, q.shape[-1], k_pool, v_pool)[:2],
                tables, pos)
    elif _choose(impl, "paged_decode_q8", mb * bs) == "pallas":
        from .pallas_attention import paged_decode_attention_q8
        return paged_decode_attention_q8(
            q, *_layer_views(layer, q.shape[-1], k_pool, v_pool, k_scale,
                             v_scale),
            tables, pos)
    return _decode_paged_fallback(
        q, k_pool, v_pool, tables, pos, k_scale, v_scale, layer, impl,
        "paged_decode" + ("" if k_scale is None else "_q8"))


def ragged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                  tables: jax.Array, pos: jax.Array,
                  impl: str = "auto", k_scale: jax.Array = None,
                  v_scale: jax.Array = None, layer=None) -> jax.Array:
    """Dispatching RAGGED batched decode attention over a paged KV pool:
    same shapes as ``paged_decode`` (q [B, Nq, D], pools [Nkv, NB, bs, D],
    tables [B, MB], pos [B] -> [B, Nq, D]) but a different contract — the
    caller passes each slot's FULL table row and TRUE position, never a
    padded bucket window shared across the batch.

    The Pallas path (ops/ragged_attention.py) grids over slots ×
    KV blocks with all heads per program and clamps each slot onto its
    own frontier, so one invocation serves the whole mixed-length batch
    at per-slot cost and the batched engine compiles ONE decode program
    for its life (no window-rung ladder, no per-rung compile churn).
    The XLA path gathers the full table and masks by ``pos`` — the
    portable fallback (default on CPU) and the byte-level correctness
    reference the parity suite pins the kernel against.  ``k_scale``/
    ``v_scale`` ([Nkv, NB, bs]) mark an int8 pool (ragged_decode_q8,
    in-VMEM dequant on the Pallas path)."""
    b, mb = tables.shape
    bs = k_pool.shape[-2]
    if k_scale is None:
        if _choose(impl, "ragged_decode", mb * bs) == "pallas":
            from .ragged_attention import ragged_paged_decode_attention
            return ragged_paged_decode_attention(
                q, *_layer_views(layer, q.shape[-1], k_pool, v_pool)[:2],
                tables, pos)
    elif _choose(impl, "ragged_decode_q8", mb * bs) == "pallas":
        from .ragged_attention import ragged_paged_decode_attention_q8
        return ragged_paged_decode_attention_q8(
            q, *_layer_views(layer, q.shape[-1], k_pool, v_pool, k_scale,
                             v_scale),
            tables, pos)
    return _decode_paged_fallback(
        q, k_pool, v_pool, tables, pos, k_scale, v_scale, layer, impl,
        "ragged_decode" + ("" if k_scale is None else "_q8"))


def _gather_verify_paged(q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                         layer=None):
    """XLA fallback for ``ragged_verify``: the SAME ``_gather_pool_seq``
    gather as ``_decode_paged_fallback`` (so the q_len=1 and q_len=γ+1
    fallbacks agree block-for-block by construction), attended through
    ``chunk_attention`` with per-query absolute positions — the
    byte-level correctness reference the Pallas verify kernels are
    pinned against."""
    g = q.shape[1]
    k_seq, v_seq = _gather_pool_seq(q, k_pool, v_pool, tables,
                                    k_scale, v_scale, layer)
    q_pos = pos[:, None] + jnp.arange(g)[None]               # [B, G]
    return chunk_attention(q, k_seq, v_seq, q_pos)


def ragged_verify(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                  tables: jax.Array, pos: jax.Array,
                  impl: str = "auto", k_scale: jax.Array = None,
                  v_scale: jax.Array = None, layer=None) -> jax.Array:
    """Dispatching RAGGED speculative-verify attention over a paged KV
    pool: q [B, G, Nq, D] — G = γ+1 chunk queries per slot at absolute
    positions ``pos[b] + g`` (``pos`` [B] is the FIRST query's position;
    the chunk's K/V are already written, write-before-attend), pools
    [Nkv, NB, bs, D], tables [B, MB] -> [B, G, Nq, D].

    The q_len=γ+1 extension of ``ragged_decode`` (the Ragged Paged
    Attention paper's q-length flexibility): the Pallas path
    (ops/ragged_attention.py verify kernels) streams each slot's own
    ceil((pos+G)/bs) blocks with a per-query causal mask, so one
    invocation verifies every slot's drafts at per-slot cost regardless
    of length skew.  The XLA path gathers the full table and reuses
    ``chunk_attention`` — the portable fallback (default everywhere
    until an on-chip A/B writes a 'pallas' row; the shipped
    ab_dispatch.json rows are conservative 'xla') and the byte-level
    parity reference.  ``k_scale``/``v_scale`` ([Nkv, NB, bs]) mark an
    int8 pool (ragged_verify_q8, in-VMEM dequant on the Pallas path)."""
    b, mb = tables.shape
    bs = k_pool.shape[-2]
    if k_scale is None:
        if _choose(impl, "ragged_verify", mb * bs) == "pallas":
            from .ragged_attention import ragged_paged_verify_attention
            return ragged_paged_verify_attention(
                q, *_layer_views(layer, q.shape[-1], k_pool, v_pool)[:2],
                tables, pos)
    elif _choose(impl, "ragged_verify_q8", mb * bs) == "pallas":
        from .ragged_attention import ragged_paged_verify_attention_q8
        return ragged_paged_verify_attention_q8(
            q, *_layer_views(layer, q.shape[-1], k_pool, v_pool, k_scale,
                             v_scale),
            tables, pos)
    return _gather_verify_paged(q, k_pool, v_pool, tables, pos,
                                k_scale, v_scale, layer)


def paged_chunk(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                table: jax.Array, start: jax.Array, q_pos: jax.Array,
                window: int, impl: str = "auto", k_scale: jax.Array = None,
                v_scale: jax.Array = None, layer=None) -> jax.Array:
    """Dispatching suffix-chunk attention over a paged KV pool
    (engine/paged_kv.chunk_prefill_paged): q [1, S_c, Nq, D], pools
    [Nkv, NB, bs, D], table [MB], start [1], q_pos [1, S_c] clamped
    absolute positions, static ``window``.  The Pallas path reconstructs
    positions from ``start`` (contiguous-chunk contract, like
    flash_chunk_attention); the XLA path gathers the window and masks by
    ``q_pos`` (portable / GSPMD-shardable fallback).  ``k_scale``/
    ``v_scale`` mark an int8 pool (XLA dequant path, see paged_decode)."""
    bs = k_pool.shape[-2]
    if k_scale is None and _choose(impl, "paged_chunk", window) == "pallas":
        from .pallas_attention import paged_chunk_attention
        return paged_chunk_attention(
            q, *_layer_views(layer, q.shape[-1], k_pool, v_pool)[:2], table,
            start, window)
    k_seq, v_seq = _gather_pool_seq(q, k_pool, v_pool,
                                    table[None, :window // bs],
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
