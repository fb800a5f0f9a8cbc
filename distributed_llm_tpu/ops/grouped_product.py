"""The routed experts' grouped product for few rows a group, as a Pallas
TPU kernel: ``out[i] = x[i] @ w[group of row i]``.

``x`` [R, in] holds rows sorted by group, ``w`` [G, in, out] one matrix a
group, ``sizes`` [G] how many rows each group has (their sum may be less
than R: trailing rows belong to no group and come out as zeros).  Inside
a model's layer loop ``w`` is the STACKED array of every layer's experts
with only this layer's ``sizes`` non-zero (``models/latent_moe.py``): the
kernel takes the whole array where it rests and finds its matrices by
index, so no per-layer slice is ever copied.

A decode step gives an expert a row or none (0.5-0.75 tokens an expert in
the benchmark's cells), so the product is bound by READING the touched
experts' matrices, each once.  What the kernel does about that:

- ``sizes`` reaches the kernel as its scalar-prefetch operand; one pass of
  the scalar unit over it makes the compact list of non-empty groups and
  their row ranges (a microsecond or three; as XLA operations before the
  kernel it was some eight small fusions a layer).  The kernel loops over
  that list alone: a group no row chose costs nothing, not even a grid
  step.
- ``w`` stays in HBM (``memory_space=pl.ANY``).  Each touched matrix is
  streamed ONCE through a double buffer of whole matrices in VMEM: while
  group ``s`` is multiplied, group ``s + 1`` is in flight.  A matrix is
  read as DMAs of whole rows, ``tile_bytes`` each at most (a few MB,
  contiguous where the matrix rests), cut from ``in`` as it is: the last
  one ends where the matrix ends, nothing has to divide by anything but
  the chip's own tiles (``serves``).
- ``x`` and the result stay whole in VMEM.  A touched group's rows are
  multiplied as blocks of at most ``ROW_BLOCK`` rows starting at a
  sublane tile, float32 accumulation, and merged into the result under a
  row mask.  With few rows a pass the MXU is bound by loading the matrix,
  about half of what the DMA takes: one pass a group hides behind the
  reads.

A loop inside the kernel and not a grid with the empty slots skipped: a
grid is static, so a call would step through min(rows, groups) slots
whatever the routing, and a step costs its bookkeeping whether or not its
body runs.

``grouped_ffn`` is an expert layer's whole FFN as ONE call (PR 53):
``act(x @ up) @ down`` a group, gated (``silu(x @ gate) * (x @ up)``) or
not (``relu(x @ up)^2``).  A call of ``grouped_product`` a product paid
three times (twice) a layer for what a call cannot hide: its launch, its
pass over ``sizes``, its rows copied in and its result out, its FIRST
matrix read with nothing to multiply meanwhile and its LAST multiplied
with nothing in flight, and XLA's small fusion of the activation between
two calls: 8-10 us a call at every shape the cells run, where a matrix
streams in 10-16 (PERF.md section 5).  The one call makes the list of
touched groups once and runs ONE sequence of matrices through the
pipeline, for group ``s``: (gate_s,) up_s, down_s; ``a``, ``u`` and ``h =
act(...)`` stay in VMEM.  It rounds where the chain of calls rounds:
float32 accumulation in each product, ``a`` and ``u`` to ``x``'s dtype (a
call's result), ``h`` once after the activation (``activation``: in
float32, as the TPU's compiler takes it in the chain): interpreted on the
CPU it equals the chain bit for bit (tests/test_grouped_product.py); on
the chip the exponential and the quotient are Mosaic's and not XLA's, and
the benchmark's ``correct`` is the judge.  ``serves_ffn`` is its static test; where it refuses,
``latent_moe.expert_ffn`` runs the chain.  **The kernel's name,
``grouped_product_ffn``, has to keep the prefix ``grouped_product``**: the
benchmark's reader finds the experts' device time by it
(benchmark/layer_metrics/cca_moe_readers.py).

``serves`` is the static test ``latent_moe._grouped`` makes on shapes:
what the kernel holds in VMEM has to fit, the chip's compiler has to be
able to cut a matrix out of the stack by index, and rows a group have to
be few enough that a group is a pass or two.  Read on the chip at the four
shapes the benchmark's cells run (scripts/grouped_product_shapes.py;
PERF.md section 5): 660-716 GB/s of the touched bytes, ``ragged_dot``
271-619.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention

LANES = 128
ROW_ALIGN = 16           # a bfloat16 tile's sublanes; a multiple of float32's 8
ROW_BLOCK = 128          # rows a pass: what the MXU streams against one load
TILE_BYTES = 6 << 20     # one DMA of a matrix's rows, at most
CHUNK_COLS = 1024        # columns a product in the kernel, at most
                         # (3, 6, 16 MB and 512-4096 columns read alike)
VMEM_BUDGET = 96 << 20   # of a v5e core's 128 MiB
# Mean rows a group (of ALL the groups given, stacked layers included)
# up to which the kernel serves: measured on the chip (PERF.md section 5).
MAX_ROWS_A_GROUP = 16


def _split(total: int, most: int, unit: int) -> List[Tuple[int, int]]:
    """(start, length) of ``total`` (whole ``unit``s) cut into equal
    pieces of whole ``unit``s, ``most`` each at most, the last one ending
    with ``total``."""
    count = -(-total // max(unit, most // unit * unit))
    length = -(-total // (count * unit)) * unit
    return [(c, min(length, total - c)) for c in range(0, total, length)]


def dma_tiles(k: int, n: int, itemsize: int,
              tile_bytes: int = TILE_BYTES) -> List[Tuple[int, int]]:
    """(first row, rows) of the DMAs one [k, n] matrix is read in: whole
    rows (contiguous where the matrix rests), ``tile_bytes`` at most."""
    return _split(k, tile_bytes // (n * itemsize), ROW_ALIGN)


def row_blocking(rows: int) -> Tuple[int, int]:
    """(rows as padded, rows a block).  Up to ROW_BLOCK rows are one
    block, the whole of ``x``; beyond, blocks start at any multiple of
    ROW_ALIGN, so the padding leaves room for a last block."""
    padded = -(-rows // ROW_ALIGN) * ROW_ALIGN
    if padded <= ROW_BLOCK:
        return padded, padded
    return padded + ROW_BLOCK, ROW_BLOCK


def vmem_bytes(rows: int, k: int, n: int, itemsize: int) -> int:
    """What a call holds in VMEM: the double buffer of matrices, ``x``
    and the result, a block's float32 product and its merge."""
    padded, block = row_blocking(rows)
    return (2 * k * n * itemsize + padded * (k + n) * itemsize
            + 3 * block * n * 4)


def serves(rows: int, groups: int, k: int, n: int, dtype) -> bool:
    """The static test: does the kernel take this product?  Floating
    rows; matrices the chip's compiler can cut by index where they rest
    (``out`` whole lane-widths, ``in`` whole sublane tiles); few rows a
    group; and everything the call keeps in VMEM fits."""
    dtype = jnp.dtype(dtype)
    return (jnp.issubdtype(dtype, jnp.floating)
            and n % LANES == 0 and k % ROW_ALIGN == 0
            and rows <= MAX_ROWS_A_GROUP * groups
            and vmem_bytes(rows, k, n, dtype.itemsize) <= VMEM_BUDGET)


def _touched(sizes_ref, gid, lo):
    """The non-empty groups in order: ``gid[s]`` the group, its rows
    ``lo[s]`` up to ``lo[s + 1]`` (rows are sorted by group, so one
    group's end is the next one's start); returns how many.  A pass of
    the scalar unit over ``sizes``: an empty group's entry is written
    over by the next."""
    def note(g, carry):
        n, row = carry
        size = sizes_ref[g]
        gid[n] = g
        lo[n] = row
        # (No more groups have a row than there are rows: the list's
        # last entry is as far as a wrong ``sizes`` gets.)
        return (jnp.minimum(n + (size > 0).astype(jnp.int32),
                            gid.shape[0] - 1), row + size)

    count, total = jax.lax.fori_loop(0, sizes_ref.shape[0], note,
                                     (jnp.int32(0), jnp.int32(0)))
    lo[count] = total
    return count


def _row_blocks(first, end, block: int, one_block: bool, merge) -> None:
    """``merge(r0)`` over the blocks of ``block`` rows that hold rows
    ``first`` up to ``end``: the whole of ``x`` where it is one block,
    else blocks from the sublane tile ``first`` lies in."""
    if one_block:
        merge(0)
        return
    base = first // ROW_ALIGN * ROW_ALIGN

    def rows_block(i, carry):
        merge(pl.multiple_of(base + i * block, ROW_ALIGN))
        return carry

    jax.lax.fori_loop(0, pl.cdiv(end - base, block), rows_block, 0)


def _kernel(sizes_ref, x_ref, w_ref, o_ref, buf, sem, gid, lo, *, tiles,
            chunks, block: int):
    one_block = block == x_ref.shape[0]

    def copy(group, slot, j):
        rows = pl.ds(*tiles[j])
        return pltpu.make_async_copy(w_ref.at[group, rows],
                                     buf.at[slot, rows], sem.at[slot, j])

    def fetch(group, slot):
        for j in range(len(tiles)):
            copy(group, slot, j).start()

    count = _touched(sizes_ref, gid, lo)

    @pl.when(count > 0)
    def _first():
        fetch(gid[0], 0)

    o_ref[...] = jnp.zeros_like(o_ref)

    def one_group(s, carry):
        @pl.when(s + 1 < count)
        def _next():
            fetch(gid[s + 1], (s + 1) % 2)

        first, end = lo[s], lo[s + 1]

        def merge(r0):
            rows = pl.ds(r0, block)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            keep = (row >= first) & (row < end)
            for c0, width in chunks:
                cols = slice(c0, c0 + width)
                y = jnp.dot(x_ref[rows, :], buf[s % 2, :, cols],
                            preferred_element_type=jnp.float32)
                o_ref[rows, cols] = jnp.where(keep, y.astype(o_ref.dtype),
                                              o_ref[rows, cols])

        for j in range(len(tiles)):
            copy(gid[s], s % 2, j).wait()
        _row_blocks(first, end, block, one_block, merge)
        return carry

    jax.lax.fori_loop(0, count, one_group, 0)


@functools.partial(jax.jit, static_argnames=("tile_bytes",))
def grouped_product(x: jax.Array, w: jax.Array, sizes: jax.Array, *,
                    tile_bytes: int = TILE_BYTES) -> jax.Array:
    """x [R, in] sorted by group, w [G, in, out], sizes [G] -> [R, out]
    in ``x``'s dtype; float32 accumulation; rows past ``sum(sizes)`` are
    zeros.  ``in`` whole sublane tiles and ``out`` whole lane-widths
    (``serves``)."""
    rows, k = x.shape
    groups, _, n = w.shape
    if k % ROW_ALIGN or n % LANES:
        raise ValueError(f"grouped_product: matrices of {k} x {n} are not "
                         f"whole tiles of {ROW_ALIGN} x {LANES}")
    itemsize = jnp.dtype(x.dtype).itemsize
    padded, block = row_blocking(rows)
    tiles = dma_tiles(k, n, itemsize, tile_bytes)
    slots = min(rows, groups)       # groups that can have a row
    xp = jnp.pad(x, ((0, padded - rows), (0, 0)))
    kernel = functools.partial(
        _kernel, tiles=tuple(tiles), block=block,
        chunks=tuple(_split(n, CHUNK_COLS, LANES)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[pltpu.VMEM((2, k, n), w.dtype),
                            pltpu.SemaphoreType.DMA((2, len(tiles))),
                            pltpu.SMEM((slots + 1,), jnp.int32),
                            pltpu.SMEM((slots + 1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((padded, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(rows, k, n, itemsize) + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(slots * k * n + rows * (k + n))
            * itemsize),
        name="grouped_product",
        # Looked up at the call: tools steer ``_interpret`` there.
        interpret=pallas_attention._interpret(),
    )(sizes.astype(jnp.int32), xp, w)
    return out[:rows]


# -- an expert layer's whole FFN as one call ----------------------------------

def ffn_vmem_bytes(rows: int, k: int, f: int, n: int, itemsize: int,
                   gated: bool) -> int:
    """What a ``grouped_ffn`` call holds in VMEM: the matrices' slots (a
    gate's and an up's, or an up's, and a down's), ``x``, ``h`` and the
    result, a block's float32 product and its merge."""
    padded, block = row_blocking(rows)
    return (((2 if gated else 1) * k * f + f * n) * itemsize
            + padded * (k + f + n) * itemsize + 3 * block * max(f, n) * 4)


def serves_ffn(rows: int, groups: int, k: int, f: int, n: int, dtype,
               gated: bool) -> bool:
    """The static test: does ONE call take the experts' whole FFN of a
    layer ([in, F] up (and gate), [F, out] down)?  Where ``serves`` takes
    each product, and everything the one call keeps in VMEM fits."""
    return (serves(rows, groups, k, f, dtype)
            and serves(rows, groups, f, n, dtype)
            and ffn_vmem_bytes(rows, k, f, n, jnp.dtype(dtype).itemsize,
                               gated) <= VMEM_BUDGET)


def activation(a, u=None):
    """``silu(a) * u``, or ``relu(a)^2`` where ``u`` is None, taken in
    float32 and rounded ONCE to ``a``'s dtype: what the TPU's compiler
    makes of the expression written in bfloat16 (it keeps a fusion's
    elementwise operations in float32; XLA's CPU compiler rounds after
    each, the logistic's four among them), written out so that the chain
    of calls and the one fused call round alike on every backend.  The
    logistic is spelled by its quotient: Mosaic lowers no bfloat16
    ``logistic``, and this is the order XLA expands it in."""
    a32 = a.astype(jnp.float32)
    if u is None:
        a32 = jnp.maximum(a32, 0.0)
        return (a32 * a32).astype(a.dtype)
    return (a32 * (1.0 / (1.0 + jnp.exp(-a32))) * u.astype(jnp.float32)
            ).astype(a.dtype)


def _ffn_kernel(sizes_ref, x_ref, *refs, gated: bool, up_tiles, down_tiles,
                f_chunks, n_chunks, block: int):
    """The matrices of the touched groups are ONE sequence, for group
    ``s``: (gate_s,) up_s, down_s.  While one is multiplied the next is
    in flight, whichever of the three it is, so a slot a matrix in flight
    and a slot the one multiplied are all the sequence needs: gate and up
    each have a slot of the [in, F] shape, down one of [F, out]."""
    stacks = refs[:2 + gated]                       # (gate,) up, down
    o_ref, abuf, bbuf, h_ref, asem, bsem, gid, lo = refs[2 + gated:]
    one_block = block == x_ref.shape[0]
    stages = len(stacks)
    down = stages - 1
    bufs = (abuf,) * down + (bbuf,)
    sems = (asem,) * down + (bsem,)
    slot_of = tuple(range(down)) + (0,)
    tiles_of = (up_tiles,) * down + (down_tiles,)

    def copies(stage, group):
        slot = slot_of[stage]
        return [pltpu.make_async_copy(
                    stacks[stage].at[group, pl.ds(*rows)],
                    bufs[stage].at[slot, pl.ds(*rows)],
                    sems[stage].at[slot, j])
                for j, rows in enumerate(tiles_of[stage])]

    def fetch(stage, group):
        for copy in copies(stage, group):
            copy.start()

    count = _touched(sizes_ref, gid, lo)

    @pl.when(count > 0)
    def _first():
        fetch(0, gid[0])

    o_ref[...] = jnp.zeros_like(o_ref)

    def one_group(s, carry):
        group, first, end = gid[s], lo[s], lo[s + 1]

        def product(rows, slot, cols):
            return jnp.dot(x_ref[rows, :], abuf[slot, :, cols],
                           preferred_element_type=jnp.float32
                           ).astype(h_ref.dtype)

        # ``a``, ``u`` and ``h`` rounded where the chain of calls rounds
        # them (a call's result, ``activation``'s), and never out of
        # VMEM; a block's rows of OTHER groups hold this group's numbers
        # for a moment, which the down product's mask drops.
        def gate_stage(r0):
            rows = pl.ds(r0, block)
            for c0, width in f_chunks:
                cols = slice(c0, c0 + width)
                h_ref[rows, cols] = product(rows, 0, cols)

        def up_stage(r0):
            rows = pl.ds(r0, block)
            for c0, width in f_chunks:
                cols = slice(c0, c0 + width)
                u = product(rows, down - 1, cols)
                h_ref[rows, cols] = (activation(h_ref[rows, cols], u)
                                     if gated else activation(u))

        def down_stage(r0):
            rows = pl.ds(r0, block)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            keep = (row >= first) & (row < end)
            for c0, width in n_chunks:
                cols = slice(c0, c0 + width)
                y = jnp.dot(h_ref[rows, :], bbuf[0, :, cols],
                            preferred_element_type=jnp.float32)
                o_ref[rows, cols] = jnp.where(keep, y.astype(o_ref.dtype),
                                              o_ref[rows, cols])

        bodies = (gate_stage,) * gated + (up_stage, down_stage)
        for stage, body in enumerate(bodies):
            if stage + 1 < stages:
                fetch(stage + 1, group)
            else:
                @pl.when(s + 1 < count)
                def _next():
                    fetch(0, gid[s + 1])
            for copy in copies(stage, group):
                copy.wait()
            _row_blocks(first, end, block, one_block, body)
        return carry

    jax.lax.fori_loop(0, count, one_group, 0)


@functools.partial(jax.jit, static_argnames=("tile_bytes",))
def grouped_ffn(x: jax.Array, w_gate, w_up: jax.Array, w_down: jax.Array,
                sizes: jax.Array, *, tile_bytes: int = TILE_BYTES
                ) -> jax.Array:
    """x [R, in] sorted by group, w_up [G, in, F] (and w_gate, or None:
    experts without a gate), w_down [G, F, out], sizes [G] -> [R, out] in
    ``x``'s dtype, a group: ``silu(x @ gate) * (x @ up)``, or ``relu(x @
    up)^2`` without a gate, then ``@ down``: the numbers of the chain of
    ``grouped_product`` calls with ``activation`` between them (float32
    accumulation; each product and the activation rounded to ``x``'s
    dtype); rows past ``sum(sizes)`` are zeros.  ``serves_ffn`` says which
    shapes."""
    gated = w_gate is not None
    rows, k = x.shape
    groups, _, f = w_up.shape
    n = w_down.shape[2]
    if k % ROW_ALIGN or f % LANES or n % LANES:
        raise ValueError(f"grouped_ffn: matrices of {k} x {f} and {f} x {n} "
                         f"are not whole tiles of {ROW_ALIGN} x {LANES}")
    itemsize = jnp.dtype(x.dtype).itemsize
    padded, block = row_blocking(rows)
    up_tiles = dma_tiles(k, f, itemsize, tile_bytes)
    down_tiles = dma_tiles(f, n, itemsize, tile_bytes)
    slots = min(rows, groups)       # groups that can have a row
    stacks = ((w_gate,) if gated else ()) + (w_up, w_down)
    xp = jnp.pad(x, ((0, padded - rows), (0, 0)))
    kernel = functools.partial(
        _ffn_kernel, gated=gated, block=block,
        up_tiles=tuple(up_tiles), down_tiles=tuple(down_tiles),
        f_chunks=tuple(_split(f, CHUNK_COLS, LANES)),
        n_chunks=tuple(_split(n, CHUNK_COLS, LANES)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[vmem] + [pl.BlockSpec(memory_space=pl.ANY)
                               for _ in stacks],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((len(stacks) - 1, k, f), w_up.dtype),
                pltpu.VMEM((1, f, n), w_down.dtype),
                pltpu.VMEM((padded, f), x.dtype),
                pltpu.SemaphoreType.DMA((len(stacks) - 1, len(up_tiles))),
                pltpu.SemaphoreType.DMA((1, len(down_tiles))),
                pltpu.SMEM((slots + 1,), jnp.int32),
                pltpu.SMEM((slots + 1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((padded, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=ffn_vmem_bytes(rows, k, f, n, itemsize, gated)
            + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * f * ((len(stacks) - 1) * k + n),
            transcendentals=rows * f if gated else 0,
            bytes_accessed=(slots * f * ((len(stacks) - 1) * k + n)
                            + rows * (k + n)) * itemsize),
        # The benchmark's reader finds the experts' device time by the
        # prefix ``grouped_product``.
        name="grouped_product_ffn",
        # Looked up at the call: tools steer ``_interpret`` there.
        interpret=pallas_attention._interpret(),
    )(sizes.astype(jnp.int32), xp, *stacks)
    return out[:rows]
