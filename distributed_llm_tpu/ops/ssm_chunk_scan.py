"""Mamba-1's recurrence over a chunk of one sequence, as a Pallas TPU
kernel: ``S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) (x) B_t``, ``y_t = S_t
C_t``, a position at a time IN ORDER.

The decay is a number a channel AND state, so the recurrence has no
matrix form over the chunk (models/hybrid_ssm.py's, for a scalar decay a
head).  As XLA operations it is either an associative scan (some ten
passes over ``[T, state, inner]`` float32, 84 MB at 256 x 16 x 5120) or
the loop unrolled at trace time (models/shared_kv_hybrid.py
``scan_unrolled``: right, and the chip's compiler takes five minutes a
program over its 256 bodies); a loop the compiler lowers to a ``while`` is
not allowed inside a layer (the benchmark tells a tick from a chunk
program by how deep its ``while``s nest).  Here the loop is inside the
kernel: the grid runs over blocks of ``LANES`` channels, each keeps its
``[state, LANES]`` float32 slice of the state in registers and steps
through the chunk's positions, reading a row of ``dt`` and ``u`` and
writing a row of ``y`` a step.  ``B`` and ``C`` (a number a state a
position, shared by every channel) come in already spread over a lane
width ``[T, state, LANES]``, so a step takes its two ``[state, LANES]``
tiles by index and nothing is transposed in the kernel; the same block
serves every grid step and is fetched once.

Positions past the chunk's valid rows carry ``dt = 0`` from the caller:
they neither decay nor feed the state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention

LANES = 128
NAME = "ssm_chunk_scan"


def serves(steps: int, state: int, inner: int) -> bool:
    """Whole lane-widths of channels, whole sublane tiles of states and
    positions, and ``B`` and ``C`` spread over a lane width fit the
    kernel's share of VMEM twice over (double buffers)."""
    return (inner % LANES == 0 and state % 8 == 0 and steps % 8 == 0
            and 2 * 2 * steps * state * LANES * 4 <= 12 << 20)


def _kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s_ref):
    a = a_ref[...]                                        # [N, LANES]

    def step(t, state):
        dt = dt_ref[pl.ds(t, 1), :]                       # [1, LANES]
        state = (jnp.exp(dt * a) * state
                 + (dt * u_ref[pl.ds(t, 1), :]) * b_ref[t])
        y_ref[pl.ds(t, 1), :] = jnp.sum(state * c_ref[t], axis=0,
                                        keepdims=True)
        return state

    s_ref[...] = jax.lax.fori_loop(0, dt_ref.shape[0], step, s0_ref[...])


def ssm_chunk_scan(dt, u, b, c, a, state):
    """dt, u [T, inner], b, c [T, state], a (= -exp(A_log)) and state
    [state, inner], all float32.  Returns (y [T, inner], the state after
    the chunk)."""
    t, inner = dt.shape
    n = state.shape[0]
    spread = (t, n, LANES)
    rows = pl.BlockSpec((t, LANES), lambda i: (0, i))
    cols = pl.BlockSpec((n, LANES), lambda i: (0, i))
    whole = pl.BlockSpec(spread, lambda i: (0, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(inner // LANES,),
        in_specs=[rows, rows, whole, whole, cols, cols],
        out_specs=[rows, cols],
        out_shape=[jax.ShapeDtypeStruct((t, inner), jnp.float32),
                   jax.ShapeDtypeStruct((n, inner), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name=NAME,
        # Looked up at the call: tools steer ``_interpret`` there.
        interpret=pallas_attention._interpret(),
    )(dt, u, jnp.broadcast_to(b[:, :, None], spread),
      jnp.broadcast_to(c[:, :, None], spread), a, state)
