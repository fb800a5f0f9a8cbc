"""Plain reference: a dense decoder-only transformer in float32.

RMSNorm, rotary embeddings (rotate-half), grouped-query attention, SwiGLU,
output head tied to the embedding (for Mistral-7B-v0.3 a departure from
the published model, which the configuration's file lists).  Straight
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no cache,
no kernels, no batching tricks; the whole sequence goes through every
layer, one layer at a time, its weights upcast to float32 as it is used.

It takes nothing from the program.  The weights come from the seed by the
recipe the configuration's file states (normal(0, 0.02) in the model's
dtype from ``jax.random.PRNGKey(seed)`` split eight ways; gains 1), which
is written out again here.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02


def init_weights(model: Dict[str, Any], seed: int, sharding=None
                 ) -> Dict[str, Any]:
    """Seeded weights in the model's own dtype (bfloat16), made on the
    device in one jitted call."""
    h, f = model["hidden_size"], model["intermediate_size"]
    n_layers = model["num_hidden_layers"]
    d = h // model["num_attention_heads"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    dtype = jnp.dtype(model.get("torch_dtype", "bfloat16"))

    def make(seed):
        # The seed is an ARGUMENT of the compiled program: as a constant
        # the compiler folds 1.7e9 random numbers at compile time, anew
        # for every seed.
        ks = jax.random.split(jax.random.PRNGKey(seed), 8)

        def normal(key, shape):
            return (WEIGHT_STD * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)
        return {
            "embed": normal(ks[0], (model["vocab_size"], h)),
            "wq": normal(ks[1], (n_layers, h, nq * d)),
            "wk": normal(ks[2], (n_layers, h, nkv * d)),
            "wv": normal(ks[3], (n_layers, h, nkv * d)),
            "wo": normal(ks[4], (n_layers, nq * d, h)),
            "w_gate": normal(ks[5], (n_layers, h, f)),
            "w_up": normal(ks[6], (n_layers, h, f)),
            "w_down": normal(ks[7], (n_layers, f, h)),
        }
    return jax.jit(make, out_shardings=sharding)(jnp.int32(seed))


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x [B, S, N, D] at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(model, x, w):
    """One decoder layer over whole sequences.  x [B, S, H] float32; w
    the layer's weights in their stored dtype."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, s, h = x.shape
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = h // nq
    eps = float(model.get("rms_norm_eps", 1e-5))
    theta = float(model.get("rope_theta", 10000.0))
    y = _rms(x, eps)
    q = _rope((y @ w["wq"]).reshape(b, s, nq, d), theta)
    k = _rope((y @ w["wk"]).reshape(b, s, nkv, d), theta)
    v = (y @ w["wv"]).reshape(b, s, nkv, d)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, s, nq * d) @ w["wo"]
    y = _rms(x, eps)
    return x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


def logits(model: Dict[str, Any], weights: Dict[str, Any], tokens,
           keep) -> jax.Array:
    """Float32 logits [B, K, V] of a full forward pass over ``tokens``
    [B, S] (right-padded; padding never reaches an earlier position) at
    the positions ``keep`` [B, K]."""
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, w: _layer(model, x, w))
        embed = weights["embed"].astype(jnp.float32)
        x = embed[tokens]
        for i in range(model["num_hidden_layers"]):
            x = layer(x, {k: v[i] for k, v in weights.items()
                          if k != "embed"})
        x = _rms(x, float(model.get("rms_norm_eps", 1e-5)))
        kept = jnp.take_along_axis(x, keep[:, :, None], axis=1)
        return jax.jit(lambda a, e: a @ e.T)(kept, embed)
