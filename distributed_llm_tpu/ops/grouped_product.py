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

    # The non-empty groups in order: ``gid[s]`` the group, its rows
    # ``lo[s]`` up to ``lo[s + 1]`` (rows are sorted by group, so one
    # group's end is the next one's start).  A pass of the scalar unit
    # over ``sizes``: an empty group's entry is written over by the next.
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
        if one_block:
            merge(0)
        else:
            base = first // ROW_ALIGN * ROW_ALIGN

            def rows_block(i, carry):
                merge(pl.multiple_of(base + i * block, ROW_ALIGN))
                return carry

            jax.lax.fori_loop(0, pl.cdiv(end - base, block), rows_block, 0)
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
