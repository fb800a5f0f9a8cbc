"""Mamba-1's recurrence over a chunk of one sequence, as a Pallas TPU
kernel: ``S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) (x) B_t``, ``y_t = S_t
C_t``, a position at a time IN ORDER.

The decay is a number a channel AND state, so the recurrence has no
matrix form over the chunk (models/hybrid_ssm.py's, for a scalar decay a
head).  As XLA operations it is either an associative scan (some ten
passes over ``[T, state, inner]`` float32, 84 MB at 256 x 16 x 5120) or
the loop unrolled at trace time (models/hybrid_ssm.py ``scan_unrolled``:
right, and the chip's compiler takes five minutes a program over its 256
bodies); a loop the compiler lowers to a ``while`` is not allowed inside
a layer (the benchmark tells a tick from a chunk program by how deep its
``while``s nest).  Here the loop is inside the kernel.

**What a grid step holds** (PR 47).  A block of ``lane_widths(inner)``
lane-widths of channels, 8 x 128 = 1024 where ``inner`` has them: the
channels fill BOTH axes of a vector register.  The state of the block is
``state`` such ``[8, 128]`` registers (16 for Mamba-1's 16 states), the
loop's carry; ``a`` is as many more.  A step of the recurrence loads one
whole register each of ``dt`` and ``u``, takes ``B_t[n]`` and ``C_t[n]``
as SCALARS from SMEM (``[state, T]`` float32 each, prefetched whole
before the first grid step: positions minor, as XLA leaves the two
narrow slices of the projection, so the transpose moves nothing) and
splats them, so the update is ``state`` independent chains of a multiply
and an add between registers, ``y_t`` is ``state`` multiply-adds between
registers and one whole unmasked store: nothing is summed across
sublanes, nothing is one sublane wide, ``B`` and ``C`` are never spread.  The loop's body is ``SUBLANES`` = 8 positions
(one tile of the operands at rest), unrolled, so what does not depend on
the state (``exp(dt A)``, ``dt u B``) of the later positions is scheduled
beside the chain of the earlier ones; the chunk's T / 8 bodies stay a
loop.

**Where the operands rest.**  ``dt``, ``u``, ``y`` are ``[T, inner]`` and
``a`` and the state ``[state, inner]`` float32 to every caller.  Under
the chip's ``(8, 128)`` tiling such an array is, byte for byte, ``[rows /
8, inner / 128, 8, 128]``: tile after tile, 8 rows of 128 channels each.
``_tiles`` hands the kernel that view as ``[rows / 8, inner / 16, 128]``
(a transpose of the logical array that moves no byte: XLA makes it a
bitcast inside the fusions that produce and consume the operands; on the
CPU, where it does move bytes, nobody times it).  In the view, row ``j``
of a tile of lane-width ``k`` is sublane ``8 k + j``, so the register
"position ``j``, lane-widths 0..7" is ONE load with a sublane stride of 8
(``rows`` in ``_kernel``), and so is each state's register.

Positions past the chunk's valid rows carry ``dt = 0`` from the caller:
they neither decay nor feed the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention

LANES = 128
SUBLANES = 8
NAME = "ssm_chunk_scan"
# The kernel's share of VMEM (the chip's default scoped limit is 16 MB;
# the rest is the compiler's own).
VMEM_BYTES = 14 << 20
# B and C rest whole in SMEM beside the grid's own scalars.
SMEM_BYTES = 256 << 10


def lane_widths(inner: int) -> int:
    """Lane-widths of channels a grid step holds: the widest of 8, 4, 2,
    1 that divides ``inner // LANES``.  8 fills a register's sublanes
    (5120 = 5 x 8 x 128); narrower forms leave sublanes empty and serve
    the small models of the tests."""
    return next(w for w in (8, 4, 2, 1) if (inner // LANES) % w == 0)


def serves(steps: int, state: int, inner: int) -> bool:
    """Whole lane-widths of channels and whole tiles of 8 states and 8
    positions (the view of ``_tiles``), and the chunk's operands fit:

    - VMEM: a grid step's blocks of ``dt``, ``u`` and ``y`` (``steps`` x
      ``lane_widths`` x 128 float32 each) and of ``a`` and the state in
      and out (``state`` rows each), twice over (double buffers):
      2 x 4 B x 128 w x (3 steps + 3 state) <= 14 MB of the 16 MB a
      kernel may use; at w = 8 and 16 states 24.6 KB a position, so 256
      positions take 6.7 MB and a chunk may be 576 positions long (384
      before PR 47, when ``B`` and ``C`` rested in VMEM spread over a lane
      width, 32 KB a position at any width of the block);
    - SMEM: ``B`` and ``C`` whole as ``[state, steps]``, 2 x 4 B x state
      x steps <= 256 KB of the chip's 1 MB (32 KB at 256 x 16; 128 B a
      position at 16 states, so VMEM binds first at every served
      width)."""
    if inner % LANES or state % SUBLANES or steps % SUBLANES:
        return False
    block = LANES * lane_widths(inner) * 4
    return (2 * block * 3 * (steps + state) <= VMEM_BYTES
            and 2 * 4 * steps * state <= SMEM_BYTES)


def _tiles(x):
    """[rows, inner] -> [rows / 8, inner / 16, 128], sublane ``8 k + j``
    of slab ``i`` holding row ``8 i + j`` of lane-width ``k``: the same
    bytes under the chip's (8, 128) tiling."""
    rows, inner = x.shape
    return x.reshape(rows // SUBLANES, SUBLANES, inner // LANES, LANES
                     ).transpose(0, 2, 1, 3).reshape(
                         rows // SUBLANES, inner // LANES * SUBLANES, LANES)


def _untiles(x, rows: int, inner: int):
    return x.reshape(rows // SUBLANES, inner // LANES, SUBLANES, LANES
                     ).transpose(0, 2, 1, 3).reshape(rows, inner)


def _kernel(b_ref, c_ref, dt_ref, u_ref, a_ref, s0_ref, y_ref, s_ref):
    n = a_ref.shape[0] * SUBLANES
    w = a_ref.shape[1] // SUBLANES

    def rows(i, j):
        """Row ``j`` of slab ``i`` over the block's ``w`` lane-widths."""
        return (i, pl.ds(j, w, stride=SUBLANES), slice(None))

    def of_state(k):
        return rows(*divmod(k, SUBLANES))

    a = [a_ref[of_state(k)] for k in range(n)]

    def tile(i, state):
        state = list(state)
        for j in range(SUBLANES):
            dt = dt_ref[rows(i, j)]
            fed = dt * u_ref[rows(i, j)]
            at = i * SUBLANES + j
            y = None
            for k in range(n):
                state[k] = (jnp.exp(dt * a[k]) * state[k]
                            + fed * b_ref[k, at])
                term = state[k] * c_ref[k, at]
                y = term if y is None else y + term
            y_ref[rows(i, j)] = y
        return tuple(state)

    state = jax.lax.fori_loop(
        0, dt_ref.shape[0], tile,
        tuple(s0_ref[of_state(k)] for k in range(n)))
    for k in range(n):
        s_ref[of_state(k)] = state[k]


def ssm_chunk_scan(dt, u, b, c, a, state):
    """dt, u [T, inner], b, c [T, state], a (= -exp(A_log)) and state
    [state, inner], all float32.  Returns (y [T, inner], the state after
    the chunk)."""
    # Looked up at the call: tools steer ``_interpret`` there.
    return _scan(dt, u, b, c, a, state,
                 interpret=pallas_attention._interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _scan(dt, u, b, c, a, state, *, interpret: bool):
    """The call, under a ``jit`` of its own for the TRACE's sake: the
    kernel's body is 8 x ``state`` updates written out in Python (0.4 s to
    trace and lower), a chunk program holds the call 13 times at one
    shape and an engine warms a program a window rung; so it is traced
    once a process and lowered once a program (a Jamba chunk program
    lowers in 0.8 s where it took 5.6: compile for a described v5e, PR
    47).  XLA inlines the call: one custom call a layer, as before."""
    t, inner = dt.shape
    n = state.shape[0]
    w = lane_widths(inner)
    rows, cols = (pl.BlockSpec((r // SUBLANES, w * SUBLANES, LANES),
                               lambda i, *_: (0, i, 0)) for r in (t, n))
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                 # B and C, in SMEM
            grid=(inner // LANES // w,),
            in_specs=[rows, rows, cols, cols],
            out_specs=[rows, cols]),
        out_shape=[jax.ShapeDtypeStruct(
            (r // SUBLANES, inner // LANES * SUBLANES, LANES), jnp.float32)
            for r in (t, n)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name=NAME,
        interpret=interpret,
    )(b.T, c.T, _tiles(dt), _tiles(u), _tiles(a), _tiles(state))
    return _untiles(y, t, inner), _untiles(state, n, inner)
