"""The Pallas flash prefill, and what every kernel of this package asks
about the backend.

``flash_causal_attention``: blocked prefill attention with the online-
softmax (flash) recurrence: KV blocks stream through VMEM, the [S, S]
score matrix is never materialized in HBM, and the causal frontier prunes
whole KV blocks (block j is skipped entirely once j*BK > (i+1)*BQ).
float32 running max / sum / accumulator, bfloat16 everywhere else, the
MXU-native mix.  A custom VJP recomputes attention with the XLA path on
the backward pass so the same kernel serves training (flash backward
trades FLOPs for the O(S²) residuals it refuses to store).

It is the one attention kernel here (``ops.attention.causal`` calls it
where an engine opted into kernels); the decode tick's is
``ops/rows_attention.py``.  ``_interpret`` / ``kernel_mode`` are what the
package's Pallas kernels (that one, ``ops/grouped_product.py``,
``ops/ssm_chunk_scan.py``) ask at the call: compiled on the TPU,
interpreted on the host CPU, so the CPU test suite exercises the kernel
code the TPU compiles.

Layouts: the public contract matches ops/attention.py ([B, S, N, D]); the
kernel internally uses head-major [B, N, S, D] so the last two dims tile
onto (sublane, lane).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, causal_attention


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
