"""Ring attention: exact attention over sequence-sharded inputs.

Long-context sequence parallelism for prompts that exceed one chip's HBM or
compute budget: Q/K/V are sharded along the sequence axis over an 'sp' mesh
axis; each device holds one block and K/V blocks rotate around the ring via
``ppermute`` while every device accumulates its queries' attention with a
flash-style streaming softmax (running max + normalizer), so the full S×S
score matrix never materializes and communication overlaps compute around
the ICI ring.  The reference has no analogue (SURVEY.md §5.7 — its context
handling is conversational hygiene only); this is a new TPU-native
capability required for first-class long-context serving.

Exactness: matches ops.attention.causal_attention up to float tolerance
(tested on a virtual CPU mesh in tests/test_parallel.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF, _expand_kv


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    head_axis: str | None = None,
) -> jax.Array:
    """q: [B, S, N_q, D]; k/v: [B, S, N_kv, D], S sharded over ``axis_name``.

    Returns [B, S, N_q, D] with the same sharding.  ``head_axis`` names a
    second mesh axis sharding the head dim (2-D sp×tp serving meshes) so
    tensor-parallel shards keep only their own heads through the ring —
    omitted, heads are treated as replicated over every other mesh axis.
    """
    n_shards = mesh.shape[axis_name]
    groups = q.shape[2] // k.shape[2]

    spec = P(None, axis_name, head_axis, None)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def run(q_blk, k_blk, v_blk):
        return _ring_block(q_blk, k_blk, v_blk, axis_name=axis_name,
                           n_shards=n_shards, groups=groups, causal=causal)

    return run(q, k, v)


def _ring_block(q, k, v, *, axis_name: str, n_shards: int, groups: int,
                causal: bool) -> jax.Array:
    """Per-device body: stream all K/V blocks past the local Q block."""
    b, s_local, n_q, d = q.shape
    my_idx = jax.lax.axis_index(axis_name)
    scale = d ** -0.5

    k = _expand_kv(k, groups)
    v = _expand_kv(v, groups)
    qf = q.astype(jnp.float32)

    # Streaming-softmax accumulators.
    m = jnp.full((b, n_q, s_local), NEG_INF, jnp.float32)        # running max
    l = jnp.zeros((b, n_q, s_local), jnp.float32)                # normalizer
    acc = jnp.zeros((b, s_local, n_q, d), jnp.float32)

    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
    local_pos = jnp.arange(s_local)

    def accumulate(i, m, l, acc, k_blk, v_blk):
        """Fold one K/V block into the streaming softmax accumulators."""
        # After i forward rotations, this device holds block (my_idx - i).
        src = (my_idx - i) % n_shards

        logits = jnp.einsum("bqnd,bknd->bnqk", qf,
                            k_blk.astype(jnp.float32)) * scale

        if causal:
            q_pos = my_idx * s_local + local_pos                  # [s_local]
            k_pos = src * s_local + local_pos
            mask = q_pos[:, None] >= k_pos[None, :]               # [sq, sk]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            valid = mask[None, None]
        else:
            valid = jnp.ones_like(logits, dtype=bool)

        blk_max = jnp.max(logits, axis=-1)                        # [b,n,sq]
        new_m = jnp.maximum(m, blk_max)
        # Re-mask after the shift so fully-masked blocks contribute zero
        # (finite NEG_INF sentinel keeps exp() well-defined).
        p_ij = jnp.where(valid, jnp.exp(logits - new_m[..., None]), 0.0)
        correction = jnp.exp(m - new_m)

        l = l * correction + jnp.sum(p_ij, axis=-1)
        acc = (acc * correction.transpose(0, 2, 1)[..., None]
               + jnp.einsum("bnqk,bknd->bqnd", p_ij, v_blk.astype(jnp.float32)))
        return new_m, l, acc

    def step(i, carry):
        m, l, acc, k_blk, v_blk = carry
        m, l, acc = accumulate(i, m, l, acc, k_blk, v_blk)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m, l, acc, k_blk, v_blk

    # Rotate n_shards-1 times; the final resident block is folded in outside
    # the loop so no wasted trailing ppermute burns ICI bandwidth.
    m, l, acc, k_last, v_last = jax.lax.fori_loop(
        0, n_shards - 1, step, (m, l, acc, k, v))
    m, l, acc = accumulate(n_shards - 1, m, l, acc, k_last, v_last)

    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]   # [b,sq,n,1]
    return (acc / denom).astype(q.dtype)
