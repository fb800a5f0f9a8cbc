"""The latent family's CHUNK attention by blocks of the window (ISSUE 61):
a chunk's queries against the cached latent rows of its table's window,
the rows up-projected to keys and values a block at a time beside their
use.

Both forms of ``models/latent_moe.py`` ``_attend``'s chunk stage
(``absorbed=False``) are here, chosen by ``serves``.  The plain form
(``plain``) up-projects the WHOLE gathered window to ``[W, N, dn + dv]``,
writes ``[N, S, W]`` float32 scores and their probabilities and reads all
three back: at 64 heads a chunk of 256 against 16 384 positions 1.07 GB of
scores, 0.54 GB of probabilities and 0.54 GB of keys and values a layer,
36 of the chunk program's 52 ms (ledger, PR 59).  By blocks
(``latent_chunk_attention``) none of the three rests in HBM.

**A grid step** is (sequence, ``heads_a_step`` heads, a block of
``block_rows`` window rows).  The block's rows arrive in VMEM as they rest
(``[block_rows, R]``: the latent numbers ``[:dc]``, the shared rotary
numbers ``[dc:dc + dr]``, then the row's padding, which nothing reads);
the step's heads' slabs of ``w_kvb`` stay while the window passes.  Inside,
two loops over refs, each traced ONCE: the block's live ``sub_rows``-row
pieces (a piece wholly past the chunk's last query position is the chunk's
own future: neither multiplied nor, a whole block of them, fetched), and
the step's heads.  For a piece and a head: ``c @ w_kvb[head]`` in float32,
rounded to the rows' dtype where ``quant.matmul`` rounds it; the scores
``q_nope . k + q_rope . k_r`` in float32, times the softmax scale, masked
``col <= q_pos``; the online softmax in float32 (running maximum, sum and
output a head in VMEM); the probabilities rounded to the dtype before the
value product, as ``p.astype(dtype)`` does.  The same mathematics at the
same stated precision: a softmax's sums are taken in another order.

**What a trace costs** (the price at start-up: PR 60's kernel of this
name was refused for 16 s of ``setup_s``).  Heads and window blocks are
grid axes, pieces and the step's heads ``fori_loop``s (two heads written
out a pass, whatever the count of heads): the kernel's jaxpr holds the
same equations at 4 heads x 256 as at 64 x 16 384.  The call
sits under a ``jit`` of its own (``_blocks``), as ``ssm_chunk_scan._scan``
does: a program's two sites (the inline lead layer and the scan's body)
share one trace, a process traces a shape once.  And ``serves`` admits
only the shapes where the kernel pays.
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
NAME = "latent_chunk_attention"
VMEM_LIMIT = 64 << 20
# Heads a grid step: the block of rows is fetched once for them all, so
# its DMA falls from a step's whole time (one head: 1.3 MB a 1024 rows
# beside 2.2 us of products) to an eighth of it; past 8 nothing is left to
# hide and the heads' slabs of ``w_kvb``, queries and sums only grow (4, 8
# and 16 read 3794, 3708 and 3680 us at 64 heads x 16 384, ``--tune``
# trials at pieces of 512 rows: my chip runs, PR 61).
MAX_HEADS_A_STEP = 8
# Heads a pass of the heads' loop, written out: a head is a chain of four
# products, each waiting for the one before, and with two in a body the
# unit multiplies one head's up-projection under the other's softmax
# (1, 2, 4, 8 read 3067, 2983, 2951, 2941 us there, trials at pieces of
# 1024; as committed 2972).
HEADS_UNROLLED = 2
# Window rows a piece: what one pass of the two loops multiplies.  The
# float32 temporaries (a head's up-projection, its scores and their
# exponentials) are this wide; a piece costs about 0.8 us of waiting a
# head whatever its width (256, 512, 1024, 2048 read 6059, 3708, 3065,
# 2935 us there, trials), and a chunk multiplies half a piece of its own
# future (a rung half written: 1762 us at 1024, 1875 at 2048).
MAX_SUB_ROWS = 1024
# Window rows a grid step fetches: a step costs 0.35 us before it
# multiplies anything (1024, 2048, 4096 read the same to 1 %).
MAX_BLOCK_ROWS = 2048
# Under this many bytes of plain-form temporaries a layer (``[N, S, W]``
# float32 scores, their probabilities and the up-projected window in the
# dtype) the plain form stays: see ``serves``.
PAYS_FROM_BYTES = 96 << 20


def _largest_divisor(n: int, at_most: int, of: int = 1) -> int:
    """The largest divisor of ``n`` that is a multiple of ``of`` and at
    most ``at_most`` (0 if there is none)."""
    return max((d for d in range(of, min(n, at_most) + 1, of)
                if n % d == 0), default=0)


def blocking(s: int, w: int, n: int):
    """(heads a step, window rows a block, window rows a piece) from the
    shapes: each the largest divisor of its axis under its cap, pieces of
    whole lane-widths (the scores' minor axis)."""
    sub = _largest_divisor(w, MAX_SUB_ROWS, LANES)
    block = _largest_divisor(w, MAX_BLOCK_ROWS, sub) if sub else 0
    return _largest_divisor(n, MAX_HEADS_A_STEP), block, sub


def vmem_bytes(s: int, w: int, n: int, dn: int, dr: int, dv: int, dc: int,
               row: int, itemsize: int) -> int:
    """What a grid step keeps in VMEM: the block of rows, the heads'
    slabs of ``w_kvb``, their queries and the output twice over (double
    buffers); the running output, maximum and sum a head in float32 (the
    last two a lane wide, padded to a lane-width); and a piece's
    temporaries for the heads written out a pass: the up-projection in
    float32 and in the dtype, the scores and their exponentials in
    float32, the probabilities in the dtype."""
    heads, block, sub = blocking(s, w, n)
    return (2 * itemsize * (block * row + heads * dc * (dn + dv)
                            + heads * s * (dn + dr + dv))
            + 4 * heads * s * (dv + 2 * LANES)
            + HEADS_UNROLLED * (sub * (dn + dv) * (4 + itemsize)
                                + s * sub * (2 * 4 + itemsize)))


def plain_temporaries_bytes(s: int, w: int, n: int, dn: int, dv: int,
                            itemsize: int) -> int:
    """What the plain form writes and reads back a layer: the scores in
    float32, their probabilities and the up-projected window in the
    dtype."""
    return n * s * w * (4 + itemsize) + w * n * (dn + dv) * itemsize


def serves(s: int, w: int, n: int, dn: int, dr: int, dv: int, dc: int,
           row: int, dtype) -> bool:
    """The static test: does the kernel take a chunk of ``s`` queries a
    sequence against a window of ``w`` rows ``row`` wide?

    Floating rows (an int8 pool's scales ride beside its rows: the plain
    form widens them); the latent numbers, a head's keys and its values
    whole lane-widths each (the kernel cuts a row and a head's
    up-projection by lane index) and the row holding both parts; whole
    sublane tiles of queries; a window of whole lane-widths; and what a
    step keeps fits VMEM.

    And the shape PAYS.  Every admitted ``(s, w)`` is a trace and a
    lowering of the kernel at warm-up (0.1 s a program in the sandbox),
    whether a request ever runs it, and the kernel's gain is the plain
    form's temporaries, which grow with ``n * s * w``: XLA keeps small ones
    near the unit and the plain form then wins.  Measured a layer (ONE run
    of ``scripts/latent_chunk_attention_shapes.py`` on the tree as
    committed, my chip runs, PR 61; plain against blocks, us): 256 queries
    x 256 rows at 64 heads, 32 MiB of temporaries, 90 against 164; at 32
    heads, 16 MiB, 83 against 116; 64 queries x 1024 rows at 32 heads, 28
    MiB, 142 against 135, and 256 x 1024 at 32 heads, 64 MiB, 175 against
    173: ties; 256 x 2048 at 32 heads, 128 MiB, 417 against 258; 256 x
    1024 at 64 heads, 128 MiB, 445 against 267; 64 x 8192 at 32 heads, 224
    MiB, 857 against 594; 256 x 16 384 at 64 heads, 2 GiB, 10 196 against
    2972.  So ``PAYS_FROM_BYTES`` lies between the last tie and the first
    clear gain: the lane's first rung stays plain everywhere, the second
    at 32 heads, and of a prefix cache's reuse suffixes only those over
    the span's rung go by blocks."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return False
    tile = 32 // dtype.itemsize
    if (dc % LANES or dn % LANES or dv % LANES or row < dc + dr
            or s % tile or w % LANES):
        return False
    return (plain_temporaries_bytes(s, w, n, dn, dv, dtype.itemsize)
            >= PAYS_FROM_BYTES
            and vmem_bytes(s, w, n, dn, dr, dv, dc, row, dtype.itemsize)
            <= VMEM_LIMIT // 2)


def einsum_f32(spec: str, a, b):
    """Einsum of the operands as stored, accumulated and returned in
    float32.  The CPU backend has no bfloat16 x bfloat16 -> float32
    product, so there the operands are widened first: the same numbers."""
    if jax.default_backend() == "cpu":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def plain(q_nope, q_rope, rows, w_kvb, q_pos, *, scale: float):
    """The plain form, which ``latent_moe._attend`` serves where ``serves``
    says no and the tests, the smoke and
    ``scripts/latent_chunk_attention_shapes.py`` hold the kernel to: the
    whole window up-projected and rounded to the dtype, ``[N, S, W]``
    float32 scores, ``softmax``, the probabilities rounded before the value
    product.  Arguments and result as ``latent_chunk_attention``'s."""
    b, _, n, dn = q_nope.shape
    dc, dr = w_kvb.shape[0], q_rope.shape[-1]
    kvb = (rows[..., :dc] @ w_kvb.reshape(dc, -1)).reshape(
        b, -1, n, w_kvb.shape[-1])
    scores = (einsum_f32("bsnd,bwnd->bnsw", q_nope, kvb[..., :dn])
              + einsum_f32("bsnr,bwr->bnsw", q_rope, rows[..., dc:dc + dr]))
    seen = (jnp.arange(rows.shape[1])[None, None, None, :]
            <= q_pos[:, None, :, None])
    p = jax.nn.softmax(jnp.where(seen, scores * scale, NEG_INF), axis=-1)
    return einsum_f32("bnsw,bwnd->bsnd", p.astype(rows.dtype),
                      kvb[..., dn:]).astype(rows.dtype)


def _kernel(last_ref, pos_ref, qn_ref, qr_ref, rows_ref, w_ref, o_ref,
            m_ref, l_ref, acc_ref, *, dc: int, dn: int, dr: int, sub: int,
            scale: float):
    """Grid: (sequences, head blocks, window blocks), the window last and
    in order.  ``last_ref`` [B] (SMEM) the sequence's last query position;
    ``pos_ref`` [1, S, 1] the queries' positions; ``qn_ref`` / ``qr_ref``
    [1, heads, S, dn | dr]; ``rows_ref`` [1, block, R]; ``w_ref`` [heads,
    dc, dn + dv]; ``o_ref`` [1, heads, S, dv]."""
    b, j = pl.program_id(0), pl.program_id(2)
    heads, block = w_ref.shape[0], rows_ref.shape[1]
    unrolled = _largest_divisor(heads, HEADS_UNROLLED)
    dtype = rows_ref.dtype
    contract_minor = (((1,), (1,)), ((), ()))

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = pos_ref[0]                                          # [S, 1]
    first = j * block
    # The block's pieces that hold a position some query sees.
    live = jnp.clip(pl.cdiv(last_ref[b] + 1 - first, sub), 0, block // sub)

    def piece(t, _):
        at = pl.multiple_of(t * sub, sub)
        c = rows_ref[0, pl.ds(at, sub), :dc]
        k_r = rows_ref[0, pl.ds(at, sub), dc:dc + dr]
        col = first + at + jax.lax.broadcasted_iota(
            jnp.int32, (q_pos.shape[0], sub), 1)
        seen = col <= q_pos

        def head(h):
            kv = jnp.dot(c, w_ref[h], preferred_element_type=jnp.float32
                         ).astype(dtype)                  # [sub, dn + dv]
            s = (jax.lax.dot_general(qn_ref[0, h], kv[:, :dn],
                                     contract_minor,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[0, h], k_r, contract_minor,
                                       preferred_element_type=jnp.float32))
            s = jnp.where(seen, s * scale, NEG_INF)             # [S, sub]
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(dtype), kv[:, dn:],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        def group(g, _):
            for k in range(unrolled):
                head(g * unrolled + k)
            return 0

        return jax.lax.fori_loop(0, heads // unrolled, group, 0)

    jax.lax.fori_loop(0, live, piece, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # Position 0 is in every query's sight: no sum is zero.
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def latent_chunk_attention(q_nope, q_rope, rows, w_kvb, q_pos, *,
                           scale: float):
    """q_nope [B, S, N, dn], q_rope [B, S, N, dr] (rotated where the
    pattern rotates), rows [B, W, R] the gathered window as it rests (the
    latent numbers ``[:dc]``, the shared rotary numbers ``[dc:dc + dr]``),
    w_kvb [dc, N, dn + dv] in the rows' dtype, q_pos [B, S] -> [B, S, N,
    dv]: query (b, s) over the columns ``<= q_pos[b, s]``."""
    # Looked up at the call: tools steer ``_interpret`` there.
    return _blocks(q_nope, q_rope, rows, w_kvb, q_pos, scale=scale,
                   interpret=pallas_attention._interpret())


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _blocks(q_nope, q_rope, rows, w_kvb, q_pos, *, scale: float,
            interpret: bool):
    """The call, under a ``jit`` of its own for the TRACE's sake
    (``ssm_chunk_scan._scan``): a chunk program holds it at two sites of
    one shape and an engine warms a program a window rung, so it is traced
    once a process and lowered once a program."""
    b, s, n, dn = q_nope.shape
    dr = q_rope.shape[-1]
    w, row = rows.shape[1:]
    dc, dv = w_kvb.shape[0], w_kvb.shape[-1] - dn
    heads, block, sub = blocking(s, w, n)

    def by_head(width):
        return pl.BlockSpec((1, heads, s, width),
                            lambda b_, h, j, last: (b_, h, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, dc=dc, dn=dn, dr=dr, sub=sub,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n // heads, w // block),
            in_specs=[
                pl.BlockSpec((1, s, 1), lambda b_, h, j, last: (b_, 0, 0)),
                by_head(dn), by_head(dr),
                # A block wholly in the chunk's future is not fetched: the
                # index stays at the last live one.
                pl.BlockSpec((1, block, row), lambda b_, h, j, last: (
                    b_, jnp.minimum(j, last[b_] // block), 0)),
                pl.BlockSpec((heads, dc, dn + dv),
                             lambda b_, h, j, last: (h, 0, 0)),
            ],
            out_specs=by_head(dv),
            scratch_shapes=[pltpu.VMEM((heads, s, 1), jnp.float32),
                            pltpu.VMEM((heads, s, 1), jnp.float32),
                            pltpu.VMEM((heads, s, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, n, s, dv), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=NAME,
        interpret=interpret,
    )(jnp.max(q_pos, axis=1).astype(jnp.int32),
      q_pos.astype(jnp.int32)[..., None],
      q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3), rows,
      w_kvb.transpose(1, 0, 2))
    return out.transpose(0, 2, 1, 3)
