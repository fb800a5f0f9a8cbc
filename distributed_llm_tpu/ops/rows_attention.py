"""One-token decode attention over the token-major K/V pool WHERE IT
RESTS (ISSUE 45): the served tick's window is read once for K and once
for V, by the operation that multiplies it.

The pool of engine/paged_kv.py is ``[L, NB, bs, N_kv * D]``: whole
blocks of whole tokens, the heads side by side on the lanes.  The XLA
form of the tick (``ops.attention.merged_decode_attention``) gathers
``pool[layer, tables]`` into a window ``[B, S, N_kv * D]`` that the two
products then read: the chip's compiler never fuses that gather into
its consumer (not with one index, not with the reshape gone, not for
the shared-K/V family either: compile for a described v5e, PR 45), so
every layer of every step passes the window three times.

This kernel walks the block table instead.  The whole pool is an
operand as it rests (``memory_space=pl.ANY``: no layer slice, no
head-major view, nothing window-sized between the pool and the softmax)
and each slot's program copies ITS live blocks, ``(layer, tables[b, j])``
for ``j <= pos[b] // bs``, into VMEM itself, ``g`` a chunk, the next
chunk in flight while this one is worked on.  A block wholly past its
slot's position is what the mask zeroes anyway: it is neither fetched
nor multiplied, so a slot costs its own length and an idle slot one
block.

The arithmetic is shaped by what a query of ONE token leaves the matrix
unit to do.  A product that holds a K or a V block still in the unit and
streams 32 query rows past it pays a load of the unit for every 128 x
128 of the window (2.2 us a block of 64 x 2048, a third of the memory's
rate: my chip run, PR 45).  So each slot makes two passes over its table:

* K pass: the chunk's rows ARE the streamed operand, against the spread
  query held still ([Nq, C]: row ``n`` holds query head ``n`` at its own
  ``D`` columns, zeros elsewhere — the merged form's zeros, which add
  nothing — contracted over ``C`` on both sides): scores ``[positions,
  Nq]`` in float32, masked by position, kept in VMEM for the whole
  window, their maximum a head carried beside them.
* V pass: ``p = exp(scores - max) / sum`` in the rows' dtype (the sum is
  final before the first V block is touched, so nothing is rescaled
  between blocks: the XLA form's softmax-then-cast, to the bit on the
  CPU), spread over the lanes by a 0/1 matrix held still (``E`` [Nq, C]:
  query head ``n`` to its own ``D`` columns), multiplied with the V
  chunk elementwise in float32 and summed over positions on the vector
  unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention
from .pallas_attention import NEG_INF

LANES = 128
# Positions a chunk holds (whole table blocks): the float32 products of
# the V pass, [positions, C] twice, are what a program keeps in VMEM
# beside its chunk buffers.
STEP_POSITIONS = 256
MAX_BLOCKS_A_STEP = 8
VMEM_LIMIT = 64 << 20


def blocks_a_step(table_blocks: int, block_size: int) -> int:
    """The largest divisor of the table's width, at most
    ``MAX_BLOCKS_A_STEP``, whose blocks hold ``STEP_POSITIONS``."""
    want = max(1, min(MAX_BLOCKS_A_STEP, STEP_POSITIONS // block_size))
    return max(g for g in range(1, want + 1) if table_blocks % g == 0)


def vmem_bytes(n_q: int, table_blocks: int, block_size: int, row: int,
               itemsize: int) -> int:
    """Two chunk buffers a side, a chunk's rows in float32 twice (V and
    the spread weights), the window's scores (a head a lane, padded to a
    lane width), the spread query and the 0/1 matrix twice each."""
    chunk = blocks_a_step(table_blocks, block_size) * block_size
    return (2 * 2 * chunk * row * itemsize + 2 * chunk * row * 4
            + table_blocks * block_size * max(n_q, LANES) * 4
            + 2 * 2 * row * n_q * itemsize)


def serves(n_q: int, d: int, table_blocks: int, block_size: int, row: int,
           dtype) -> bool:
    """The static test: does the kernel take this tick's window?
    Floating rows (an int8 pool's scales ride beside its rows: the XLA
    form dequantizes them); a K/V head to every query head, because the
    V pass spreads the weights over ``Nq * D`` lanes and its vector work
    grows with the query heads a K/V head serves while the rows shrink
    (GQA 32/8 at head 64, rows of 1 KB: 44 us a layer against the XLA
    form's 33; 32/2 at head 128, rows of 512 B: 142 against 35: my chip
    run, PR 45); rows of whole lane-widths and blocks of whole sublane
    tiles, so a block is cut by index where it rests; and everything a
    program keeps in VMEM fits."""
    dtype = jnp.dtype(dtype)
    return (jnp.issubdtype(dtype, jnp.floating)
            and n_q * d == row
            and row % LANES == 0
            and block_size % (32 // dtype.itemsize) == 0
            and vmem_bytes(n_q, table_blocks, block_size, row,
                           dtype.itemsize) <= VMEM_LIMIT // 2)


def _kernel(tables_ref, pos_ref, layer_ref, q_ref, e_ref, k_hbm, v_hbm,
            o_ref, k_buf, v_buf, sem, s_ref, *, bs: int, g: int,
            scale: float):
    """Grid: B.  One slot a program: its live blocks, ``g`` a chunk, by
    DMAs of its own from the pool where it rests into one of two chunk
    buffers a side, the next chunk in flight while this one is worked
    on — and V's first chunk behind K's last."""
    b = pl.program_id(0)
    pos, layer = pos_ref[b], layer_ref[0]

    def live_blocks(slot):                     # blocks with a position in
        return jnp.minimum(pos_ref[slot] // bs + 1, tables_ref.shape[1])

    live = live_blocks(b)
    chunks = pl.cdiv(live, g)
    span = g * bs
    row = k_buf.shape[-1]

    # A chunk's blocks past the slot's last live one are not fetched: what
    # rests in the buffer in their place is masked (K) or weighted by an
    # exact zero (V), so it only has to be finite — an earlier chunk's
    # rows, or the zeros the first program leaves.
    @pl.when(b == 0)
    def _finite():
        v_buf[...] = jnp.zeros_like(v_buf)

    def copies(side, c, slot, act, b_=b, live_=live):
        hbm, buf = ((k_hbm, k_buf), (v_hbm, v_buf))[side]
        for i in range(g):
            @pl.when(c * g + i < live_)
            def _(i=i):
                act(pltpu.make_async_copy(
                    hbm.at[layer, tables_ref[b_, c * g + i]],
                    buf.at[slot, pl.ds(i * bs, bs)], sem.at[side, slot, i]))

    def fetch(side, c, slot, **whose):
        copies(side, c, slot, lambda copy: copy.start(), **whose)

    def arrived(side, c, slot):
        copies(side, c, slot, lambda copy: copy.wait())

    # K's first chunk: the program before this one has asked for it.
    @pl.when(b == 0)
    def _first():
        fetch(0, 0, 0)

    def scores(c, m):
        slot = c % 2

        @pl.when(c + 1 < chunks)
        def _next():
            fetch(0, c + 1, 1 - slot)

        @pl.when(c + 1 == chunks)
        def _values_first():
            fetch(1, 0, 0)

        arrived(0, c, slot)
        first = pl.multiple_of(c * span, span)
        s = jax.lax.dot_general(
            k_buf[slot], q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [span, Nq]
        at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + first
        s = jnp.where(at <= pos, s, NEG_INF)
        s_ref[pl.ds(first, span), :] = s
        return jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))

    m = jax.lax.fori_loop(0, chunks, scores,
                          jnp.full((1, q_ref.shape[1]), NEG_INF,
                                   jnp.float32))

    # The K buffers are free from here on: the next slot's first chunk
    # arrives while this slot's V pass runs.
    @pl.when(b + 1 < pl.num_programs(0))
    def _next_slot():
        fetch(0, 0, 0, b_=b + 1, live_=live_blocks(b + 1))

    def weights(c):
        first = pl.multiple_of(c * span, span)
        return jnp.exp(s_ref[pl.ds(first, span), :] - m)

    total = jax.lax.fori_loop(
        0, chunks,
        lambda c, t: t + jnp.sum(weights(c), axis=0, keepdims=True),
        jnp.zeros_like(m))
    share = 1.0 / total                        # position 0 is always in

    def values(c, acc):
        slot = c % 2

        @pl.when(c + 1 < chunks)
        def _next():
            fetch(1, c + 1, 1 - slot)

        p = (weights(c) * share).astype(e_ref.dtype)
        spread = jnp.dot(p, e_ref[...],
                         preferred_element_type=jnp.float32)  # [span, C]
        arrived(1, c, slot)
        v = v_buf[slot].astype(jnp.float32)
        return acc + (spread * v).reshape(-1, 8, row).sum(axis=0)

    acc = jax.lax.fori_loop(0, chunks, values,
                            jnp.zeros((8, row), jnp.float32))
    o_ref[0] = jnp.sum(acc, axis=0, keepdims=True).astype(o_ref.dtype)


def paged_rows_decode_attention(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, tables: jax.Array,
                                pos: jax.Array, layer) -> jax.Array:
    """q [B, Nq, D], pools [L, NB, bs, C = Nkv * D] whole, tables [B, MB],
    pos [B], ``layer`` a traced scalar -> [B, Nq, D]: ``decode_attention``
    of each slot over positions ``<= pos[b]`` of its table's window,
    scores and sums in float32."""
    b, n_q, d = q.shape
    bs, row = k_pool.shape[2], k_pool.shape[3]
    assert n_q * d == row, "a K/V head to every query head (``serves``)"
    mb = tables.shape[1]
    g = blocks_a_step(mb, bs)

    # E[n, (j, d)]: 1 where j == n, the D columns that are head n's own;
    # the spread query is E with head n's query on its ones.
    e = jnp.repeat(jnp.eye(n_q, dtype=q.dtype), d, axis=1)       # [Nq, C]
    q_rows = e[None] * q.reshape(b, 1, row)

    rests = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n_q, row), lambda b_, *_: (b_, 0, 0)),
                  pl.BlockSpec((n_q, row), lambda b_, *_: (0, 0)),
                  rests, rests],
        out_specs=pl.BlockSpec((1, 1, row), lambda b_, *_: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, g * bs, row), k_pool.dtype),
            pltpu.VMEM((2, g * bs, row), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, g)),
            pltpu.VMEM((mb * bs, n_q), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, g=g, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, row), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        # Looked up at the call: tools steer ``_interpret`` there.
        interpret=pallas_attention._interpret(),
        name="paged_rows_decode",
    )(tables.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_rows, e, k_pool, v_pool
      ).reshape(b, n_q, d)
