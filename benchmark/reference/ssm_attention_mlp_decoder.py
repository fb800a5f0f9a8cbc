"""Plain reference: the state-space / attention decoder with a dense gated
MLP in every layer (AI21's Jamba block at ``num_experts`` 1, ``jamba``) in
float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
one sequence at a time goes whole through one layer at a time; the
state-space recurrence is a plain ``scan`` over time from a zero state;
attention is the naive masked softmax over all positions, a block of query
positions at a time (no cache); nothing is kept between calls.  It takes
nothing from the program or the harness.

``num_hidden_layers`` layers, no positional term anywhere, no bias but the
two named.  Layer ``l``: ``h = x + Mixer_l(RMSNorm(x))``, then ``x' = h +
W_down(silu(W_gate n) * (W_up n))`` with ``n = RMSNorm(h)``.  ``Mixer_l``
is attention where ``l % attn_layer_period == attn_layer_offset``, else
Mamba.  After the last layer a final RMSNorm; logits ``x E^T`` with the
tied embedding.

    Mamba      [a | z] = n W_in;  a <- silu(conv_K(a) + b_c)  (depthwise,
               causal);  [delta | B | C] = a W_x;
               delta <- RMSNorm(delta; g_dt), B <- RMSNorm(B; g_B),
               C <- RMSNorm(C; g_C);  dt = softplus(delta W_dt + b_dt)
               A = -exp(A_log) [state, inner]
               S_t = exp(dt_t A) * S_{t-1} + (dt_t a_t) (x) B_t
               y_t = S_t C_t + D * a_t;   out = (y * silu(z)) W_out
    attention  q = n W_q (heads x d), k = n W_k, v = n W_v (K/V heads x d),
               query head h reads K/V head h // (heads / K/V heads), causal
               softmax(q k^T / sqrt(d)) v, W_o

Departures from the published ``config.json`` (the configuration file
lists them under ``assumed``): ``head_dim`` = hidden / heads; the three
inner RMSNorms and the time step's init range are the ``jamba`` model
code's, not keys of the config; ``expert_layer_*`` say nothing at
``num_experts`` 1 (every layer's MLP is dense).

Weights come from the seed by the recipe of the program's
``models/hybrid_ssm.py``, written out again here.  The program counts
SUBLAYERS (a mixer, an MLP: ``2 * num_hidden_layers``): ``PRNGKey(seed)``
split 3 ways (embedding, a head's key that a tied model does not use,
layers), the layers' key split a sublayer (the mixer of layer ``l`` takes
key ``2 l``, its MLP ``2 l + 1``), a sublayer's key split 8 ways;
normal(0, 0.02) rounded to the model's dtype (the embedding normal(0, 1),
drawn a block of rows at a time: the largest divisor of the vocabulary
that is at most 4096); the pre-norms' and the final norm's gains 1; the
three INNER norms' gains 1 + normal(0, 0.1) (the mixer's eighth key split
3 ways: delta's, B's, C's), so that a dropped gain moves the logits; the
conv and its bias uniform in +-1/sqrt(taps); ``W_dt`` uniform in
+-rank^-0.5; the time step's bias ``dt`` log-uniform in [time_step_min,
time_step_max] floored at time_step_floor, stored as softplus's inverse;
``A_log`` = log(1..state) a channel, ``D`` 1.  A layer's weights are made
when the layer is run and dropped after it.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_STD = 0.02
INNER_NORM_STD = 0.1
EMBED_STD = 1.0
TABLE_ROWS = 4096       # most rows of the vocabulary table drawn at a time
QUERY_BLOCK = 512       # query positions whose [heads, block, T] scores are held
VOCAB_BLOCK = 16384     # rows of the head widened to float32 at a time
LENGTH_STEP = 64        # a sequence is run to its last kept position, rounded up


# -- weights from the seed ---------------------------------------------------

def _dtype(model):
    return jnp.dtype(model.get("torch_dtype", "bfloat16"))


def _normal(key, shape, dtype, std=WEIGHT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _uniform(key, shape, dtype, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


def _table(key, rows, width, dtype, std):
    block = max(n for n in range(1, TABLE_ROWS + 1) if rows % n == 0)
    keys = jax.random.split(key, rows // block)
    return jax.lax.map(lambda k: _normal(k, (block, width), dtype, std),
                       keys).reshape(rows, width)


def sizes(model):
    """(inner width, state, taps, time-step rank)."""
    return (model["mamba_expand"] * model["hidden_size"],
            model["mamba_d_state"], model["mamba_d_conv"],
            model["mamba_dt_rank"])


def head_dim(model) -> int:
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


def kinds(model) -> str:
    """The mixer of every layer: ``*`` attention, ``M`` Mamba."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return "".join("*" if l % period == offset else "M"
                   for l in range(model["num_hidden_layers"]))


def make_mixer(model, key, kind: str) -> Dict[str, Any]:
    dtype = _dtype(model)
    h = model["hidden_size"]
    ks = jax.random.split(key, 8)
    if kind == "*":
        d = head_dim(model)
        nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
        return {"w_q": _normal(ks[0], (h, nq * d), dtype),
                "w_k": _normal(ks[1], (h, nkv * d), dtype),
                "w_v": _normal(ks[2], (h, nkv * d), dtype),
                "w_o": _normal(ks[3], (nq * d, h), dtype)}
    di, n, k, r = sizes(model)
    lo, hi = math.log(model["time_step_min"]), math.log(model["time_step_max"])
    dt = jnp.exp(jax.random.uniform(ks[5], (di,), jnp.float32)
                 * (hi - lo) + lo)
    dt = jnp.maximum(dt, model["time_step_floor"])
    g_dt, g_b, g_c = (
        (1.0 + INNER_NORM_STD * jax.random.normal(key, (width,), jnp.float32)
         ).astype(dtype)
        for key, width in zip(jax.random.split(ks[7], 3), (r, n, n)))
    return {"w_in": _normal(ks[0], (h, 2 * di), dtype),
            "conv_w": _uniform(ks[1], (k, di), dtype, k ** -0.5),
            "conv_b": _uniform(ks[2], (di,), dtype, k ** -0.5),
            "w_x": _normal(ks[3], (di, r + 2 * n), dtype),
            "w_dt": _uniform(ks[4], (r, di), dtype, r ** -0.5),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "w_out": _normal(ks[6], (di, h), dtype),
            "g_dt": g_dt, "g_b": g_b, "g_c": g_c}


def make_mlp(model, key) -> Dict[str, Any]:
    dtype = _dtype(model)
    h, f = model["hidden_size"], model["intermediate_size"]
    ks = jax.random.split(key, 8)
    return {"w_gate": _normal(ks[0], (h, f), dtype),
            "w_up": _normal(ks[1], (h, f), dtype),
            "w_down": _normal(ks[2], (f, h), dtype)}


def init_weights(model: Dict[str, Any], seed: int, sharding=None
                 ) -> Dict[str, Any]:
    """Keys only: the embedding's and one a SUBLAYER; every array is made
    from its key when ``logits`` reaches it.  The seed is an argument of
    the compiled maker."""
    def make(seed):
        k_embed, _, k_layers = jax.random.split(jax.random.PRNGKey(seed), 3)
        return {"k_embed": k_embed,
                "layer_keys": jax.random.split(
                    k_layers, 2 * model["num_hidden_layers"])}
    out = jax.jit(make, out_shardings=sharding)(jnp.int32(seed))
    out["sharding"] = sharding
    return out


# -- the block ------------------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, eps, gain=None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if gain is None else y * _f32(gain)


def mamba(model, w, h):
    """h [T, H] -> the mixer's output [T, H]: the recurrence one position
    at a time from a zero state [state, inner]."""
    di, n, k, r = sizes(model)
    eps = float(model["rms_norm_eps"])
    t = h.shape[0]
    az = h @ _f32(w["w_in"])
    a, z = az[:, :di], az[:, di:]
    padded = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), a])
    a = sum(padded[j:j + t] * _f32(w["conv_w"])[j] for j in range(k))
    a = jax.nn.silu(a + _f32(w["conv_b"]))
    dbc = a @ _f32(w["w_x"])
    delta = rms_norm(dbc[:, :r], eps, w["g_dt"])
    b = rms_norm(dbc[:, r:r + n], eps, w["g_b"])
    c = rms_norm(dbc[:, r + n:], eps, w["g_c"])
    dt = jax.nn.softplus(delta @ _f32(w["w_dt"]) + w["dt_bias"])   # [T, di]
    a_mat = -jnp.broadcast_to(
        jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
        (n, di))                        # -exp(A_log), A_log = log 1..N

    def step(state, x):
        a_t, dt_t, b_t, c_t = x
        state = (jnp.exp(dt_t[None, :] * a_mat) * state
                 + (dt_t * a_t)[None, :] * b_t[:, None])
        return state, jnp.sum(state * c_t[:, None], axis=0) + a_t  # D = 1

    _, y = jax.lax.scan(step, jnp.zeros((n, di), jnp.float32),
                        (a, dt, b, c))
    return (y * jax.nn.silu(z)) @ _f32(w["w_out"])


def attention(model, w, h):
    """h [T, H] -> [T, H]: every head at once, a block of query positions
    at a time over all T keys, masked."""
    t = h.shape[0]
    d = head_dim(model)
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    q = (h @ _f32(w["w_q"])).reshape(t, nq, d)
    k = jnp.repeat((h @ _f32(w["w_k"])).reshape(t, nkv, d), nq // nkv, axis=1)
    v = jnp.repeat((h @ _f32(w["w_v"])).reshape(t, nkv, d), nq // nkv, axis=1)
    key_pos = jnp.arange(t)[None, :]
    blocks = []
    for t0 in range(0, t, QUERY_BLOCK):
        q_pos = jnp.arange(t0, min(t0 + QUERY_BLOCK, t))[:, None]
        scores = jnp.einsum("qhd,khd->hqk", q[t0:t0 + QUERY_BLOCK], k)
        scores = jnp.where(key_pos <= q_pos, scores * d ** -0.5, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        blocks.append(o.reshape(o.shape[0], nq * d))
    return jnp.concatenate(blocks, axis=0) @ _f32(w["w_o"])


def layer_forward(model, kind: str, w, w_mlp, x):
    """One layer on one sequence: x [T, H] float32 -> [T, H]; the
    pre-norms' gains are 1."""
    eps = float(model["rms_norm_eps"])
    mixer = attention if kind == "*" else mamba
    x = x + mixer(model, w, rms_norm(x, eps))
    n = rms_norm(x, eps)
    return x + (jax.nn.silu(n @ _f32(w_mlp["w_gate"]))
                * (n @ _f32(w_mlp["w_up"]))) @ _f32(w_mlp["w_down"])


def logits(model: Dict[str, Any], weights: Dict[str, Any], tokens,
           keep) -> jax.Array:
    """Float32 logits [B, K, V] of a full forward pass over ``tokens``
    [B, S] (right-padded; padding never reaches an earlier position) at
    the positions ``keep`` [B, K].  One sequence at a time through one
    layer at a time, each sequence run up to its last kept position (in
    steps of ``LENGTH_STEP``)."""
    pattern = kinds(model)
    eps = float(model["rms_norm_eps"])
    sharding = weights.get("sharding")
    dtype = _dtype(model)
    shape = (model["vocab_size"], model["hidden_size"])
    keep_host = np.asarray(keep)
    width = tokens.shape[1]
    runs = [min(width, -(-(int(k.max()) + 1) // LENGTH_STEP) * LENGTH_STEP)
            for k in keep_host]
    with jax.default_matmul_precision("highest"):
        table = jax.jit(lambda k: _table(k, *shape, dtype, EMBED_STD),
                        out_shardings=sharding)
        embed = table(weights["k_embed"])
        xs = [_f32(embed[tokens[b, :n]]) for b, n in enumerate(runs)]
        del embed                    # made again for the tied head
        # One compiled maker and one compiled layer a KIND.
        make = {kind: jax.jit(lambda k, kind=kind: make_mixer(model, k, kind),
                              out_shardings=sharding) for kind in "M*"}
        make_ffn = jax.jit(lambda k: make_mlp(model, k),
                           out_shardings=sharding)
        run = {kind: jax.jit(lambda w, w_mlp, x, kind=kind:
                             layer_forward(model, kind, w, w_mlp, x))
               for kind in "M*"}
        for l, kind in enumerate(pattern):
            w = make[kind](weights["layer_keys"][2 * l])
            w_mlp = make_ffn(weights["layer_keys"][2 * l + 1])
            for b in range(len(xs)):
                # A wait a layer a sequence: dispatch runs ahead of the
                # device, and the layers in flight would hold their
                # temporaries all at once.
                xs[b] = jax.block_until_ready(run[kind](w, w_mlp, xs[b]))
            del w, w_mlp

        kept = jnp.stack([rms_norm(x[keep_host[b]], eps)
                          for b, x in enumerate(xs)])
        rows = jax.jit(lambda a, h: a @ _f32(h).T)
        embed = table(weights["k_embed"])
        return jnp.concatenate(
            [rows(kept, embed[v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, shape[0], VOCAB_BLOCK)], axis=-1)
