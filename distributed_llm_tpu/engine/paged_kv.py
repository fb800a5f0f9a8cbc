"""Paged KV cache: block-table memory management for the decode cache.

The reference's KV memory lives inside Ollama/llama.cpp, one contiguous
context per server process (SURVEY.md §2.1); a continuous-batching engine
needs many sequences of very different lengths resident at once, so the
TPU-native design is vLLM-style paging adapted to XLA's static shapes:

- One HBM **pool** per tier, arrays of ROWS ``[L, num_blocks,
  block_size, cfg.cache_row_rest_width]``: token-major, a block a
  contiguous run of tokens and a token's row whatever the model caches
  of it — its kv heads side by side on ONE axis (``N_kv * D``, one array
  for K and one for V), or the latent family's one head-less row
  (``"c"``, the only array: models/latent_moe.py), which rests at whole
  lane-widths with zeros behind its numbers (640 for the published 512 +
  64).  That is the order the step programs below use inside their layer
  loop (a row write and the table gather both move whole tokens), and
  with the last axis whole 128-lane widths — the heads merged, the
  latent row rounded up — the device's DEFAULT layout for the array is
  that order, row-major, on both sides of every program, and no program
  transposes the pool on its way in or out: ONE format, at rest and
  through every program (DESIGN.md "A pool array has one format";
  ``pool_formats`` says what the device holds, ``obs/program_scopes.py``
  ``pool_sized_moves`` what a compiled program copies).  (Head-major
  ``[L, N_kv, NB, bs, D]`` at head_dim 64 rested block-axis-minor and
  was transposed, whole, twice a program, PR 27; so did a latent row of
  576 over 1281 blocks, where padding the BLOCK axis to 1408 was the
  more compact tiling, PR 57; a layout pinned with
  ``jax.experimental.layout`` does not survive the persistent compile
  cache — PERF.md §6, PRs 27 and 57.)  A 'tp' mesh axis shards the merged axis
  (heads are contiguous runs of it), and a tp hook is handed a layer's
  head-major ``[N_kv, NB, bs, D]`` view (``_hooked``).
- The pool is ONE buffer that every step program updates in place: the
  layer loop CARRIES it whole (``_scan_layers``) and each layer writes
  its rows at ``(layer, block, offset)`` and gathers its window from the
  whole pool at that layer — never sliced out per layer and stacked
  back, which copied the pool every step (ROADMAP S0).
- A host-side **BlockAllocator** (free list) hands fixed-size blocks to
  slots; block 0 is reserved as a trash block that idle batch slots write
  into, so the batched decode step needs no host-side compaction.
- Each batch slot owns a **block table** row ``[max_blocks_per_slot]`` of
  pool block ids; logical position ``p`` lives at
  ``(table[p // bs], p % bs)``, so a gathered table reconstructs the
  sequence in order and the usual ``col <= pos`` mask is the ragged mask.
- ``decode_step_paged`` is the batched one-token forward: scatter this
  step's K/V into the pool (write-before-attend, like the contiguous
  path), gather each slot's logical window, and run masked decode
  attention.  All shapes are static in (max_slots, max_blocks); occupancy
  varies at runtime only through ``pos`` and the table contents.

The transformer math (RMSNorm/RoPE/GQA/SwiGLU) is imported from
models/transformer.py — this module only changes where the rows live.
The latent family's layer is models/latent_moe.py's; the three step
functions hand it the cells to write and the tables to read, and the
block programs (``copy_block``, ``gather_blocks``, ``scatter_blocks``)
work on whatever arrays the pool has.  The hybrid family's
(models/hybrid_ssm.py) pool holds K/V blocks for its ATTENTION layers
("*", and "C", whose tail row a slot is ``"t"`` with ``"s"`` empty; or
"L"'s latent rows, ``"c"`` alone)
only and, beside them, one recurrent row a slot a state-space layer
(``"s"``, ``"t"``) with the vector that says whose each row is
(``"owner"``): rows are not blocks, so the block programs refuse that
pool by name.  The shared-K/V family's (models/shared_kv_hybrid.py) pool
is of that kind with three sorts of per-sequence memory: K/V blocks for
ONE layer (read by every layer after it), a ring of the window's
positions a slot a window layer (``"rk"``, ``"rv"``) and Mamba-1's row a
slot a state-space layer.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..models import (hybrid_ssm, latent_moe, shared_kv_hybrid,
                      transformer)
from ..obs.program_scopes import row_major
from ..ops import attention, quant

KVPool = Dict[str, jax.Array]    # {"k","v": [L, NB, bs, N_kv * D]}
# int8 pools add {"ks","vs": [L, NB, bs, N_kv]} per-row dequant scales;
# the latent family's pool is {"c": [L, NB, bs, kv_lora + rope, rounded up
# to whole lane-widths]} alone.

TRASH_BLOCK = 0

# Canonical impls live in ops/quant.py (the contiguous cache shares them);
# re-exported here for the paged call sites and existing tests.
from ..ops.quant import dequantize_kv_rows, quantize_kv_rows  # noqa: E402,F401


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    block_size: int = 64
    max_slots: int = 4
    max_seq_len: int = 2048
    # Pool size override (TierConfig.kv_pool_blocks): a pool smaller than
    # full residency is the regime where KV-aware admission and
    # preemption-with-replay (engine/batching.py) actually bind.
    pool_blocks: Optional[int] = None

    @property
    def blocks_per_slot(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def num_blocks(self) -> int:
        if self.pool_blocks is not None:
            # Explicit pool budget, plus the reserved trash block.
            return self.pool_blocks + 1
        # Full residency for every slot, plus the reserved trash block.
        return self.max_slots * self.blocks_per_slot + 1


def init_pool(cfg: ModelConfig, pcfg: PagedConfig,
              kv_quantize: str = "none") -> KVPool:
    """``kv_quantize="int8"`` stores cached K/V as symmetric per-row int8
    with float32 scales — decode's KV read traffic halves (decode is
    bandwidth-bound; the KV term dominates the weight term at long
    context × batch).  Writes quantize, reads dequantize at the attention
    op (ops/attention.py paged paths)."""
    rows = (cfg.num_layers, pcfg.num_blocks, pcfg.block_size)
    shape = rows + (cfg.cache_row_rest_width,)
    if cfg.latent:
        if kv_quantize != "none":
            raise ValueError(
                f"kv_quantize={kv_quantize!r}: the latent-attention family "
                f"({cfg.name}) has no int8 pool — its one cached row a "
                f"token is not rows by heads; use 'none'")
        return {"c": jnp.zeros(shape, jnp.dtype(cfg.dtype))}
    if cfg.hybrid:
        if kv_quantize != "none":
            raise ValueError(
                f"kv_quantize={kv_quantize!r}: the state-space hybrid "
                f"family ({cfg.name}) has no int8 pool — the dense int8 "
                f"rows are not wired to its attention layers; use 'none'")
        dtype = jnp.dtype(cfg.dtype)
        # K/V blocks for the layers that own K/V by position (the hybrid
        # family's "*" and "C" layers, the shared-K/V family's ONE "F"),
        # and a ROW a slot a layer that keeps one: a state-space layer's
        # float32 state — Mamba-1's [state, inner] (channels on the
        # lanes), Mamba-2's [heads, P, N] — and its conv tail in the
        # model's dtype; a linear-attention layer's ("K") float32 MATRIX a
        # head [heads, d_k, d_v] and the tails of its three convs side by
        # side; a "C" layer's tail alone (one row of the last token's
        # inputs to its convolutions and its shifted value), the state
        # then empty.  A pattern with "L" pages ONE array of latent rows,
        # "c", where "*" and "C" page "k" and "v".  The paged array
        # first: ``_block_size`` reads the first array.
        n_c, r = cfg.layers_of("C"), pcfg.max_slots
        n_m = cfg.layers_of("M") + cfg.layers_of("K")
        state = ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_head_dim)
                 if cfg.layers_of("K") else
                 (cfg.ssm_state, cfg.ssm_inner) if cfg.ssm_dt_rank else
                 (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
        tail = ((n_c, r, 1, cfg.cca_tail_width) if n_c else
                (n_m, r, cfg.ssm_conv - 1, cfg.ssm_conv_width))
        kv = (cfg.kv_layers,) + shape[1:]
        if cfg.layers_of("L"):
            pool = {"c": jnp.zeros(kv, dtype)}
        else:
            pool = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
        if cfg.shared_kv:
            # A ring of the window's positions a slot a window layer:
            # exactly the window (models/shared_kv_hybrid.py).
            ring = (cfg.layers_of("W"), r, cfg.attn_window,
                    cfg.cache_row_width)
            pool.update(rk=jnp.zeros(ring, dtype), rv=jnp.zeros(ring, dtype))
        pool.update(s=jnp.zeros((n_m, r) + state, jnp.float32),
                    t=jnp.zeros(tail, dtype),
                    owner=jnp.zeros((r,), jnp.int32))
        return pool
    if kv_quantize == "int8":
        scales = rows + (cfg.num_kv_heads,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.ones(scales, jnp.float32),
                "vs": jnp.ones(scales, jnp.float32)}
    if kv_quantize != "none":
        raise ValueError(f"kv_quantize={kv_quantize!r}: expected 'none' "
                         "or 'int8'")
    dtype = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def pool_formats(pool: KVPool) -> Dict[str, Dict[str, Any]]:
    """Each pool array's format AT REST, as the device holds it (GET
    /stats ``tiers.<tier>.pool.formats``): its shape, the order of its
    axes in memory (``major_to_minor``), the tiling, and whether that
    order is the one a pool array rests in, row-major.  Nothing pins it
    (a pinned layout does not survive the persistent compile cache:
    PERF.md section 6, PRs 27 and 57): the device's default for the
    array's SHAPE decides, so the shapes ``init_pool`` gives are the ones
    whose default is row-major (``cfg.cache_row_rest_width``), and every
    pool program takes and returns the array as it rests."""
    out = {}
    for key, x in pool.items():
        layout = x.format.layout
        out[key] = {"shape": list(x.shape), "dtype": str(x.dtype),
                    "major_to_minor": list(layout.major_to_minor),
                    "tiling": str(layout.tiling),
                    "row_major": row_major(layout.major_to_minor)}
    return out


class BlockAllocator:
    """Thread-safe REFCOUNTED free-list over pool blocks (block 0 never
    allocated).

    Ownership model (ISSUE 10, cross-request shared-prefix KV): a block
    leaves the free list with refcount 1; ``share()`` increfs it so N
    holders — live slots mapping a shared prefix read-only, parked
    prefix-cache entries — each own one reference; ``free()`` decrefs
    and only a block reaching refcount 0 returns to the free list.
    Every holder calls the SAME ``free()`` it always did, so exclusive
    ownership (refcount 1 everywhere) behaves exactly like the
    pre-refcount allocator.  ``available`` keeps its meaning: blocks on
    the free list, i.e. what ``alloc`` can hand out right now.

    The refcount table is guarded by the allocator lock like the free
    list — refcount mutation outside it is a race the ``locks`` lint
    checker's fixtures pin (a torn incref under concurrent free would
    leak or double-free a block of live KV)."""

    def __init__(self, num_blocks: int):
        self._free: List[int] = list(range(1, num_blocks))
        self._refs: Dict[int, int] = {}
        self._lock = threading.Lock()

    def alloc(self, n: int) -> Optional[List[int]]:
        with self._lock:
            if len(self._free) < n:
                return None
            got, self._free = self._free[:n], self._free[n:]
            for b in got:
                self._refs[b] = 1
            return got

    def share(self, blocks: List[int]) -> None:
        """Incref live blocks: a new holder maps them (read-only — the
        COW contract in engine/prefix_cache.py is what keeps sharers
        from observing each other's writes).  Sharing a block that is
        not currently allocated is a lifecycle bug (the would-be sharer
        is mapping freed KV), so it raises instead of minting a
        reference to garbage."""
        with self._lock:
            bad = [b for b in blocks if self._refs.get(b, 0) < 1]
            if bad:
                raise ValueError(
                    f"share() of unallocated block(s) {bad}: only live "
                    f"blocks can gain references")
            for b in blocks:
                self._refs[b] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block; refcount-0 blocks return to the
        free list.  Freeing an unallocated block raises — a double-free
        would put the block on the free list twice and hand the same
        physical KV tile to two sequences.  The batch is validated
        BEFORE any decref, so a bad batch mutates nothing (a partial
        decref would silently leak the survivors)."""
        with self._lock:
            live = [b for b in blocks if b != TRASH_BLOCK]
            drops: Dict[int, int] = {}
            for b in live:
                drops[b] = drops.get(b, 0) + 1
            bad = [b for b, n in drops.items()
                   if self._refs.get(b, 0) < n]
            if bad:
                raise ValueError(
                    f"free() of unallocated block(s) {sorted(bad)} "
                    f"(double free)")
            released: List[int] = []
            for b, n in drops.items():
                r = self._refs[b] - n
                if r == 0:
                    del self._refs[b]
                    released.append(b)
                else:
                    self._refs[b] = r
            self._free.extend(released)

    def refcount(self, block: int) -> int:
        """Current reference count (0 = free/never allocated)."""
        with self._lock:
            return self._refs.get(block, 0)

    def refcounts(self, blocks: List[int]) -> List[int]:
        """Batch refcount read under ONE lock acquisition — the prefix
        cache's reclaimable accounting runs on the admission-gate and
        sampler paths, so a per-block lock round-trip would contend
        with the scheduler's alloc/free once per parked block."""
        with self._lock:
            return [self._refs.get(b, 0) for b in blocks]

    def ref_stats(self) -> Dict[str, int]:
        """One-lock snapshot of the sharing picture: allocated physical
        blocks, total references over them, and how many are shared
        (refcount >= 2).  ``total_refs - allocated_blocks`` is exactly
        the pool the sharing saved (kv_stats derives dedup_ratio)."""
        with self._lock:
            allocated = len(self._refs)
            total = sum(self._refs.values())
            shared = sum(1 for r in self._refs.values() if r >= 2)
            return {"allocated_blocks": allocated, "total_refs": total,
                    "shared_blocks": shared}

    @property
    def available(self) -> int:
        with self._lock:
            return len(self._free)


def write_prefill_blocks(pool: KVPool, blocks: jax.Array,
                         *rows_all: jax.Array) -> KVPool:
    """Scatter a prefilled prompt's rows into its allocated blocks.

    blocks: [nb] pool block ids; ``rows_all``: what the cold prefill
    returned, one array a pool array — K and V ``[L, S, N_kv, D]``, or
    the latent family's ``[L, S, R]`` alone — with S == nb * block_size
    (bucketed prompts divide evenly).
    """
    _blocks_only(pool, "write_prefill_blocks")
    l, s = rows_all[0].shape[:2]
    nb = blocks.shape[0]
    bs = s // nb
    # [L, S, ...] -> [L, nb, bs, ...]: already token-major.
    rows = {key: r.reshape(l, nb, bs, *r.shape[2:])
            for key, r in zip(("c",) if "c" in pool else ("k", "v"),
                              rows_all)}
    if "ks" in pool:                       # int8 pool: quantize on write
        rows["k"], rows["ks"] = quantize_kv_rows(rows["k"])
        rows["v"], rows["vs"] = quantize_kv_rows(rows["v"])
    return {key: x.at[:, blocks].set(rows[key].reshape(
        l, nb, bs, x.shape[-1])) for key, x in pool.items()}


def _blocks_only(pool: KVPool, what: str) -> None:
    """The block programs move BLOCKS; a pool with recurrent rows beside
    them (the state-space hybrid family) holds state no block carries,
    so sharing, copying or spilling its blocks would part a sequence
    from its state."""
    if "owner" in pool:
        raise NotImplementedError(
            f"{what}: the state-space hybrid family's pool keeps a "
            f"recurrent row a slot beside its K/V blocks; no block "
            f"program moves it (prefix reuse, sharing, spill and cold "
            f"prefill are refused at engine build)")


def copy_block(pool: KVPool, src: jax.Array, dst: jax.Array) -> KVPool:
    """Copy one pool block's rows (and int8 scales) from ``src`` to
    ``dst`` — the copy-on-write boundary step of shared-prefix KV
    (engine/prefix_cache.py): a slot joining a shared prefix whose
    matched length ends mid-block gets a PRIVATE copy of that partial
    block, writes its own suffix there, and the sharers never see it.

    ``src``/``dst`` are traced int32 scalars, so ONE compiled program
    serves every (src, dst) pair — the block-write program family stays
    bounded exactly like the prefill writers (a per-pair or per-length
    wrap would re-trace on the admit path; the retrace lint fixtures in
    tests/test_lint.py pin the idiom)."""
    _blocks_only(pool, "copy_block")

    def copy(x):       # one block-sized slice out, one in-place update in
        tile = jax.lax.dynamic_slice_in_dim(x, src, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(x, tile, dst, axis=1)

    return {key: copy(x) for key, x in pool.items()}


def gather_blocks(pool: KVPool, blocks: jax.Array) -> KVPool:
    """Snapshot ``blocks``' tiles of rows (and int8 scales) out of the
    pool: ``[L, nb, bs, row width]`` — the DEMOTE copy of the hierarchical KV
    spill tier (engine/kv_spill.py).  The output is a fresh functional
    array that owns its data, so the source blocks may return to the
    free list the moment this gather is *issued*: later pool writes
    build new pool arrays and can never reach the snapshot, and on
    donating backends the enqueued gather reads its input before the
    donated update may alias it.  The device→host pull of the snapshot
    happens on the spill copier thread, never here."""
    _blocks_only(pool, "gather_blocks")
    return {key: x[:, blocks] for key, x in pool.items()}


def scatter_blocks(pool: KVPool, blocks: jax.Array,
                   tiles: KVPool) -> KVPool:
    """Write previously gathered ``[L, nb, bs, row width]`` tiles back
    into ``blocks`` — the PROMOTE copy of the hierarchical KV spill
    tier.  The exact inverse of ``gather_blocks`` (bit-identical round
    trip, int8 scales included), so a promoted prefix serves decode
    exactly like one that never left the pool."""
    _blocks_only(pool, "scatter_blocks")
    return {key: x.at[:, blocks].set(tiles[key]) for key, x in pool.items()}


def pool_block_bytes(cfg: ModelConfig, block_size: int,
                     kv_quantize: str = "none") -> int:
    """Host bytes one pool block costs when spilled (its tiles of rows,
    plus int8 scales) — the unit ``TierConfig.host_kv_bytes`` budgets in.
    Shared by the engine's spill accounting and the bench's budget
    sizing so the two can never drift."""
    if cfg.latent:
        return (cfg.num_layers * block_size * cfg.cache_row_rest_width
                * jnp.dtype(cfg.dtype).itemsize)
    d = cfg.head_dim
    per_row = cfg.kv_layers * cfg.num_kv_heads * block_size
    if kv_quantize == "int8":
        # int8 k/v (1 byte) + float32 per-row scales.
        return per_row * (d * 2 + 4 * 2)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return per_row * d * itemsize * 2


_POOL_KEYS = ("k", "v", "ks", "vs")


def _block_size(pool: KVPool) -> int:
    return next(iter(pool.values())).shape[2]


def _write_rows(pools, i, blk, off, k, v):
    """Write one layer's new K/V rows into the CARRIED pool, in place.

    ``pools`` is the whole pool as the layer loop carries it — ``(k, v)``
    or, int8, ``(k, v, ks, vs)``, each ``[L, NB, bs, ·]``; ``i`` the
    traced layer index; ``blk``/``off`` ``[...]`` the block and offset
    of each row; ``k``/``v`` ``[..., N_kv, D]`` the rows (an int8 pool
    quantizes them here, a scale a head).  One scatter an array and no
    loop: the benchmark tells a decode tick from a prefill program by
    how deep its ``while``s nest."""
    rows = (k, v)
    if len(pools) == 4:
        (k, k_sc), (v, v_sc) = quantize_kv_rows(k), quantize_kv_rows(v)
        rows = (k, v, k_sc, v_sc)
    return tuple(p.at[i, blk, off].set(r.reshape(*blk.shape, p.shape[-1]))
                 for p, r in zip(pools, rows))


def _whole(pools):
    """``(k, v, ks, vs)`` of the carried pool, ``ks``/``vs`` None for a
    bf16 pool: what the dispatching ops of ops/attention.py take with
    ``layer=i`` (their XLA path gathers straight from the whole pool)."""
    return pools + (None,) * (4 - len(pools))


def _hooked(attn, q, pools, i, *where):
    """One layer's attention through an ``attn`` hook — the shard-mapped
    paths of parallel/tp_attention.py — which keeps its ``(q, kp, vp,
    *where, ks, vs)`` contract over per-layer head-major ``[N_kv, NB,
    bs(, D)]`` views: the layer sliced out of the whole pool and turned
    here, a layer-sized copy that only a hook pays."""
    def view(pool, *heads):
        x = jax.lax.dynamic_index_in_dim(pool, i, 0, keepdims=False)
        return jnp.moveaxis(x.reshape(*x.shape[:2], -1, *heads), 2, 0)

    k, v, ks, vs = _whole(pools)
    d = q.shape[-1]
    return attn(q, view(k, d), view(v, d), *where,
                ks if ks is None else view(ks),
                vs if vs is None else view(vs))


def _scan_layers(layer, x, layers, pool: KVPool):
    """The layer loop of the three paged step functions: ``xs`` is
    (layer weights, layer index) and the pool rides the CARRY whole, so
    XLA updates the one buffer in place — scanned over as ``xs`` and
    stacked back as ``ys`` it was sliced, rewritten and copied every
    step (ROADMAP S0).  ``layer(x, pools, lp, i) -> (x, pools)``."""
    keys = [key for key in _POOL_KEYS if key in pool]

    def body(carry, scanned):
        return layer(*carry, *scanned), None

    n_layers = pool[keys[0]].shape[0]
    # The loop's own slices of the stacked weights carry this scope; what
    # a layer names keeps its own.
    with jax.named_scope("layer_scan"):
        (x, pools), _ = jax.lax.scan(
            body, (x, tuple(pool[key] for key in keys)),
            (layers, jnp.arange(n_layers)))
    return x, dict(zip(keys, pools))


def chunk_prefill_paged(
    cfg: ModelConfig,
    params: transformer.Params,
    tokens: jax.Array,         # [1, S_c] right-padded suffix chunk
    start: jax.Array,          # [1] absolute position of the chunk's head
    true_len: jax.Array,       # [1] total valid length (prefix + suffix)
    pool: KVPool,
    table: jax.Array,          # [MB] the slot's block-table row
    window: int,               # static: attended positions, multiple of bs
    counts: bool = False,      # latent family: also return expert counts
) -> Tuple[jax.Array, KVPool]:
    """Prefill a prompt SUFFIX directly into pool blocks — the paged twin
    of ``transformer.chunk_prefill``, enabling session prefix reuse in the
    continuous-batching engine: a reclaimed entry's blocks become the
    slot's leading table rows and only the new turn runs here.

    Returns (hidden [1, S_c, H], updated pool).  The chunk's rows scatter
    to (table[p//bs], p%bs) per position; attention gathers the first
    window//bs table blocks, so cost is O(window), not O(max_seq).
    ``counts`` (the latent and hybrid families' engine programs): a
    third result, the chunk's assignments an expert ``[expert layers,
    num_experts]`` (hybrid: the held experts, then one column of the
    assignments that went to absent ones).  The hybrid family's chunk
    also finds or, at ``start == 0``, claims and zeroes the sequence's
    recurrent row (models/hybrid_ssm.py).  The shared-K/V family's chunk
    that does not hold the prompt's last token (``start + S_c <
    true_len``) runs its layers up to the one cached layer's K/V write
    and returns zeros for ``hidden`` (models/shared_kv_hybrid.py).
    """
    b, s_c = tokens.shape
    d = cfg.head_dim
    bs = _block_size(pool)

    with jax.named_scope("step_inputs"):
        positions = start[:, None] + jnp.arange(s_c)[None, :]    # [1, S_c]
        q_pos = jnp.minimum(positions,
                            jnp.maximum(true_len, 1)[:, None] - 1)
        flat_pos = positions[0]                                  # [S_c]
        blk = table[flat_pos // bs]                              # [S_c]
        off = flat_pos % bs
    family = cfg.family
    if family == "latent":
        hidden, new_pool, n_exp = latent_moe.forward_paged(
            cfg, params, tokens, positions, q_pos, pool, blk[None],
            off[None], table[None, :window // bs])
        return (hidden, new_pool, n_exp) if counts else (hidden, new_pool)
    if family == "shared_kv":
        ctx, pool = shared_kv_hybrid.chunk_ctx(
            pool, table, start, true_len, s_c, window, blk, off, q_pos)
        return shared_kv_hybrid.forward_paged(cfg, params, tokens, pool, ctx)
    if family == "hybrid":
        ctx, pool = hybrid_ssm.chunk_ctx(pool, table, start, true_len, s_c,
                                         window, blk, off, q_pos)
        hidden, new_pool, n_exp = hybrid_ssm.forward_paged(
            cfg, params, tokens, pool, ctx)
        return (hidden, new_pool, n_exp) if counts else (hidden, new_pool)

    x = quant.embed_rows(params["embed"], tokens)            # [1, S_c, H]
    with jax.named_scope("step_inputs"):
        sin, cos = transformer.rope_sincos(positions, d, cfg.rope_theta)

    def layer(x, pools, lp, i):
        with jax.named_scope("mixer_proj"):
            h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = transformer.project_qkv(cfg, lp, h_in)
            q = transformer.apply_rope(q, sin, cos)
            k = transformer.apply_rope(k, sin, cos)

            # Write the chunk's K/V rows to their (block, offset) cells,
            # then gather and attend the table window.
            with jax.named_scope("kv_write"):
                pools = _write_rows(pools, i, blk, off, k[0], v[0])
            with jax.named_scope("attention"):
                k_p, v_p, ks_p, vs_p = _whole(pools)
                attn = attention.paged_chunk(
                    q, k_p, v_p, table, q_pos, window,
                    k_scale=ks_p, v_scale=vs_p, layer=i)
            x = x + quant.matmul(attn.reshape(b, s_c, cfg.num_heads * d),
                                 lp["wo"])
        with jax.named_scope("ffn"):
            h_ffn = transformer.rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.num_experts > 1:
                from ..models.moe import moe_ffn_train
                ffn_out, _ = moe_ffn_train(cfg, lp, h_ffn)
                x = x + ffn_out
            else:
                x = x + transformer._swiglu(h_ffn, lp["w_gate"],
                                            lp["w_up"], lp["w_down"])
        return x, pools

    x, new_pool = _scan_layers(layer, x, params["layers"], pool)
    with jax.named_scope("head"):
        hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return hidden, new_pool


def verify_step_paged(
    cfg: ModelConfig,
    params: transformer.Params,
    tokens: jax.Array,         # [B, G] verify chunk per slot (cur + drafts)
    pos: jax.Array,            # [B] the FIRST chunk token's position
    pool: KVPool,
    tables: jax.Array,         # [B, MB] FULL table rows (ragged contract)
    attn=None,                 # (q, kp, vp, tables, pos, ks, vs) override
) -> Tuple[jax.Array, KVPool]:
    """One batched SPECULATIVE-VERIFY forward over paged caches: the
    q_len=γ+1 twin of ``decode_step_paged`` (ISSUE 15).  Each slot's
    G = γ+1 chunk tokens — the last accepted token plus its drafts —
    are embedded at absolute positions ``pos + g``, their K/V scattered
    into the slot's blocks (write-before-attend, exactly like decode),
    and ONE fused ``attention.ragged_verify`` call attends every slot's
    chunk against its own prefix with per-query causal masks, so length
    skew stays the kernel's problem.

    Returns (logits [B, G, V] float32, updated pool): row g's argmax is
    the target's pick for position ``pos + g + 1`` — the greedy
    acceptance rule compares it against draft g.  Rejected rows' K/V
    are stale garbage past the accepted frontier; the per-query mask
    (``col <= pos + g``) keeps them invisible until a later write
    overwrites them — the same overwrite-later invariant the
    sequential speculative engine and right-padded prefill rely on.
    Positions past ``max_seq_len`` (a slot finishing at the context
    edge mid-chunk) scatter into the trash block instead of clamping
    onto live KV."""
    if cfg.latent or cfg.hybrid:
        raise NotImplementedError(
            f"{cfg.name}: the latent-attention and the state-space hybrid "
            f"families have no speculative verify step (a draft model is "
            f"refused at engine build)")
    b, g = tokens.shape
    d = cfg.head_dim
    bs = _block_size(pool)
    max_pos = cfg.max_seq_len - 1

    x = quant.embed_rows(params["embed"], tokens)      # [B, G, H]
    positions = pos[:, None] + jnp.arange(g)[None]     # [B, G]
    wpos = jnp.minimum(positions, max_pos)
    with jax.named_scope("step_inputs"):
        sin, cos = transformer.rope_sincos(wpos, d, cfg.rope_theta)

    # Overflowing rows route to the reserved trash block: a clamped
    # write would land INSIDE the slot's live frontier and corrupt
    # accepted KV the per-query mask still exposes.
    blk = jnp.where(
        positions <= max_pos,
        jnp.take_along_axis(tables, wpos // bs, axis=1),
        TRASH_BLOCK)                                   # [B, G]
    off = wpos % bs
    def layer(x, pools, lp, i):
        with jax.named_scope("mixer_proj"):
            h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = transformer.project_qkv(cfg, lp, h_in)
            q = transformer.apply_rope(q, sin, cos)
            k = transformer.apply_rope(k, sin, cos)

            # Write-before-attend for the whole chunk: the [B, G] rows go to
            # (blk[b, g], off[b, g]) — trash rows collide harmlessly like
            # idle decode slots.
            with jax.named_scope("kv_write"):
                pools = _write_rows(pools, i, blk, off, k, v)

            with jax.named_scope("attention"):
                if attn is not None:
                    attn_out = _hooked(attn, q, pools, i, tables, pos)
                else:
                    k_p, v_p, ks_p, vs_p = _whole(pools)
                    attn_out = attention.ragged_verify(
                        q, k_p, v_p, tables, pos,
                        k_scale=ks_p, v_scale=vs_p, layer=i)  # [B, G, Nq, d]

            x = x + quant.matmul(
                attn_out.reshape(b, g, cfg.num_heads * d), lp["wo"])
        with jax.named_scope("ffn"):
            h_ffn = transformer.rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.num_experts > 1:
                from ..models.moe import moe_ffn_train
                ffn_out, _ = moe_ffn_train(cfg, lp, h_ffn)
                x = x + ffn_out
            else:
                x = x + transformer._swiglu(h_ffn, lp["w_gate"],
                                            lp["w_up"], lp["w_down"])
        return x, pools

    x, new_pool = _scan_layers(layer, x, params["layers"], pool)
    with jax.named_scope("head"):
        hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return transformer.logits_from_hidden(params, hidden), new_pool


def decode_step_paged(
    cfg: ModelConfig,
    params: transformer.Params,
    token: jax.Array,          # [B] current input token per slot
    pos: jax.Array,            # [B] its position (0-based)
    pool: KVPool,
    tables: jax.Array,         # [B, MB] block ids per slot
    attn=None,                 # (q, kp, vp, tables, pos, ks, vs) override
    ragged: bool = False,      # the caller's table contract (below)
    counts: bool = False,      # latent family: also return expert counts
) -> Tuple[jax.Array, KVPool]:
    """One batched autoregressive step over paged caches.

    Returns (logits [B, V] float32, updated pool).  Idle slots point their
    whole table at the trash block; their writes land there and their
    logits are ignored by the scheduler.

    Two attention contracts: the DENSE path expects callers to bound the
    gather by passing a TRUNCATED table ([B, wb] covering every active
    position — the scheduler slices to a bucketed high-water mark so
    short conversations don't stream max_seq_len of pool per step);
    ``ragged=True`` instead expects each slot's FULL table row with true
    per-slot lengths, so one compiled step serves every width.  One op
    (``attention.paged_decode``) attends either: the flag names the
    contract and changes nothing here (``benchmark/correct.py`` passes
    it; ROADMAP C0).
    The latent family attends the tables it is given under either
    contract (masked by ``pos``), in the absorbed form; ``counts`` adds
    a third result, the step's assignments an expert ``[expert layers,
    num_experts]``.  The hybrid family (windowed tables) also advances
    the recurrent rows whose first block leads one of ``tables``, and no
    other (models/hybrid_ssm.py).
    """
    b = token.shape[0]
    d = cfg.head_dim
    bs = _block_size(pool)

    with jax.named_scope("step_inputs"):
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None],
                                  axis=1)[:, 0]
        off = pos % bs                                 # [B]
    family = cfg.family
    if family == "latent":
        hidden, new_pool, n_exp = latent_moe.forward_paged(
            cfg, params, token[:, None], pos[:, None], pos[:, None], pool,
            blk[:, None], off[:, None], tables)
        logits = transformer.logits_from_hidden(params, hidden[:, 0])
        return (logits, new_pool, n_exp) if counts else (logits, new_pool)
    if family == "shared_kv":
        hidden, new_pool = shared_kv_hybrid.forward_paged(
            cfg, params, token[:, None], pool,
            shared_kv_hybrid.decode_ctx(pool, tables, pos, blk, off))
        return transformer.logits_from_hidden(params, hidden[:, 0]), new_pool
    if family == "hybrid":
        hidden, new_pool, n_exp = hybrid_ssm.forward_paged(
            cfg, params, token[:, None], pool,
            hybrid_ssm.decode_ctx(pool, tables, pos, blk, off))
        logits = transformer.logits_from_hidden(params, hidden[:, 0])
        return (logits, new_pool, n_exp) if counts else (logits, new_pool)

    x = quant.embed_rows(params["embed"], token)       # [B, H]
    with jax.named_scope("step_inputs"):
        sin, cos = transformer.rope_sincos(pos, d, cfg.rope_theta)

    def layer(x, pools, lp, i):
        with jax.named_scope("mixer_proj"):
            h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = transformer.project_qkv(cfg, lp, h_in)
            q = transformer.apply_rope(q, sin, cos)
            k = transformer.apply_rope(k, sin, cos)

            # Write-before-attend at (block, offset), one row a slot — active
            # slots hit distinct blocks, idle ones collide in trash.
            with jax.named_scope("kv_write"):
                pools = _write_rows(pools, i, blk, off, k, v)

            # Attend this slot's logical window: position p is
            # (table[p//bs], p%bs), over the carried pool WHOLE
            # (ops.attention.decode_form names the form).  An engine that
            # opted into kernels walks the block table in the kernel of
            # ops/rows_attention.py, which copies the slot's live blocks
            # from the pool where it rests (ISSUE 45); elsewhere the XLA
            # path gathers whole rows [B, S, N_kv * D] and contracts over
            # the merged axis (ops.attention.merged_decode_attention).
            # Both pay a query of one token N_kv times the multiplications
            # to never split the head axis off the window, which on a TPU
            # is a copy.  The chunk and verify steps' queries are long:
            # they keep the split ([B, S, N_kv, D]) and chunk_attention.
            with jax.named_scope("attention"):
                if attn is not None:
                    attn_out = _hooked(attn, q, pools, i, tables, pos)
                else:
                    k_p, v_p, ks_p, vs_p = _whole(pools)
                    attn_out = attention.paged_decode(
                        q, k_p, v_p, tables, pos, impl=cfg.attention_impl,
                        k_scale=ks_p, v_scale=vs_p, layer=i)

            x = x + quant.matmul(attn_out.reshape(b, cfg.num_heads * d),
                                 lp["wo"])
        with jax.named_scope("ffn"):
            h_ffn = transformer.rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.num_experts > 1:
                from ..models.moe import moe_ffn_decode
                x = x + moe_ffn_decode(cfg, lp, h_ffn)
            else:
                x = x + transformer._swiglu(h_ffn, lp["w_gate"],
                                            lp["w_up"], lp["w_down"])
        return x, pools

    x, new_pool = _scan_layers(layer, x, params["layers"], pool)
    with jax.named_scope("head"):
        hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return transformer.logits_from_hidden(params, hidden), new_pool
