"""Plain reference: the state-space / window-attention / shared-K/V
decoder-hybrid-decoder (Phi-4-mini-flash-reasoning's block, ``phi4flash``)
in float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the whole sequence goes through one layer at a time; the state-space
recurrence is a plain ``scan`` over time from a zero state; attention is
the naive full ``[T, T]`` masked softmax, every pair of heads at once and a
block of query positions at a time (no cache, no ring); nothing is kept
between calls.  It takes
nothing from the program or the harness.

Every layer ``i``: ``x <- x + mixer_i(LN1_i(x))``, then ``x <- x +
W2_i(silu(g) * u)`` with ``[g | u] = W1_i LN2_i(x)`` (no bias).  ``LN`` is
LayerNorm with gain and bias.  Logits = ``LN_f(x) E^T`` with the tied
embedding (no scaling, no head bias).  No rotary or other positional term.
The mixer by the character of ``layer_pattern``:

    M   [a | z] = h W_in;  a <- silu(conv_K(a) + b_c)   (depthwise, causal)
        [delta | B | C] = a W_x;  D_t = softplus(delta W_dt + b_dt)
        A = -exp(A_log) [d_inner, d_state]
        S_t = exp(D_t[:, None] A) * S_{t-1} + (D_t a_t)[:, None] B_t[None, :]
        m_t = S_t C_t + D * a_t;   out = (m * silu(z)) W_out
        The pattern's LAST M layer also publishes m (before the gate, the
        D term included): the memory of that position.
    W   [q | k | v] = h W_qkv + b; position t attends t-window+1 .. t;
        differential heads; out W_o + b_o
    F   as W with no window (causal)
    G   out = (m * silu(h W_1)) W_2, m the memory of the SAME position
    X   q = h W_q + b only; causal attention over layer F's k, v;
        differential heads; out W_o + b_o

Differential heads: query heads in pairs (2p, 2p+1), K/V heads in pairs;
query pair p reads K/V pair p // 2; with k_1, k_2 the pair's two key heads
and v its two value heads side by side (2 x head size wide),
``o_p = softmax(q_2p k_1^T / sqrt(d)) v - lam softmax(q_2p+1 k_2^T /
sqrt(d)) v``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
``lam_init = 0.8 - 0.6 exp(-0.3 i)`` (i the layer's index), then an
RMSNorm over the pair's width with a gain, times ``(1 - lam_init)``.

Departures from the published description (the configuration file lists
them under ``assumed``): the state-space sizes are the family's defaults
(state 16, 4 taps, expand 2, time-step rank ceil(hidden / 16)); the order
of the kinds, the biases on the attention projections, the head pairing,
``lam_init``'s depth and that the memory carries the ``D`` term are read
from the model's paper and the differential-attention paper.

Weights come from the seed by the recipe of the program's
``models/shared_kv_hybrid.py``, written out again here: ``PRNGKey(seed)``
split 3 ways (embedding, the final norm's bias, layers), the layers' key
split a layer, a
layer's key split 16 ways; normal(0, 0.02) rounded to the model's dtype
(the embedding normal(0, 1), drawn a block of rows at a time: the largest
divisor of the vocabulary that is at most 4096), LayerNorm and RMSNorm
gains 1, EVERY bias normal(0, 0.02) but the conv's (uniform in
+-1/sqrt(taps), like its taps) and the time step's (``dt`` log-uniform in
[time_step_min, time_step_max] floored at time_step_floor, stored as
softplus's inverse); ``W_dt`` uniform in +-rank^-0.5; ``A_log`` =
log(1..d_state) a channel, ``D`` 1; the four lambda vectors normal(0,
0.1) float32.  A layer's weights are made when the layer is run and
dropped after it.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02
LAMBDA_STD = 0.1
EMBED_STD = 1.0
TABLE_ROWS = 4096       # most rows of the vocabulary table drawn at a time
QUERY_BLOCK = 512       # query positions whose [pairs, 2, block, T] scores are held
VOCAB_BLOCK = 16384     # rows of the head widened to float32 at a time
KINDS = "MWFGX"


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


def make_layer(model, key, kind: str) -> Dict[str, Any]:
    dtype = _dtype(model)
    h, f = model["hidden_size"], model["intermediate_size"]
    di, n, k, r = sizes(model)
    d = h // model["num_attention_heads"]
    nq, nkv = model["num_attention_heads"] * d, \
        model["num_key_value_heads"] * d
    ks = jax.random.split(key, 16)
    w = {"ln1_b": _normal(ks[0], (h,), dtype),
         "ln2_b": _normal(ks[1], (h,), dtype),
         "w1": _normal(ks[2], (h, 2 * f), dtype),
         "w2": _normal(ks[3], (f, h), dtype)}
    if kind == "M":
        lo, hi = math.log(model["time_step_min"]), \
            math.log(model["time_step_max"])
        dt = jnp.exp(jax.random.uniform(ks[9], (di,), jnp.float32)
                     * (hi - lo) + lo)
        dt = jnp.maximum(dt, model["time_step_floor"])
        w.update(w_in=_normal(ks[4], (h, 2 * di), dtype),
                 conv_w=_uniform(ks[5], (k, di), dtype, k ** -0.5),
                 conv_b=_uniform(ks[6], (di,), dtype, k ** -0.5),
                 w_x=_normal(ks[7], (di, r + 2 * n), dtype),
                 w_dt=_uniform(ks[8], (r, di), dtype, r ** -0.5),
                 dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                 w_out=_normal(ks[10], (di, h), dtype))
    elif kind in "WFX":
        if kind == "X":
            w.update(w_q=_normal(ks[4], (h, nq), dtype),
                     b_q=_normal(ks[5], (nq,), dtype))
        else:
            w.update(w_qkv=_normal(ks[4], (h, nq + 2 * nkv), dtype),
                     b_qkv=_normal(ks[5], (nq + 2 * nkv,), dtype))
        w.update(w_o=_normal(ks[6], (nq, h), dtype),
                 b_o=_normal(ks[7], (h,), dtype),
                 lam=LAMBDA_STD * jax.random.normal(ks[8], (4, d),
                                                    jnp.float32))
    else:
        w.update(w_g1=_normal(ks[4], (h, di), dtype),
                 w_g2=_normal(ks[5], (di, h), dtype))
    return w


def init_weights(model: Dict[str, Any], seed: int, sharding=None
                 ) -> Dict[str, Any]:
    """Keys only: the embedding's, the final norm's and one a layer; every array is made
    from its key when ``logits`` reaches it.  The seed is an argument of
    the compiled maker."""
    def make(seed):
        k_embed, k_final, k_layers = jax.random.split(
            jax.random.PRNGKey(seed), 3)
        return {"k_embed": k_embed, "k_final": k_final,
                "layer_keys": jax.random.split(
                    k_layers, model["num_hidden_layers"])}
    out = jax.jit(make, out_shardings=sharding)(jnp.int32(seed))
    out["sharding"] = sharding
    return out


# -- the block ------------------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def layer_norm(x, bias, eps):
    """Gain 1."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) + _f32(bias)


def mamba(model, w, h):
    """h [T, H] -> (mixer output [T, H], the memory m [T, inner]): the
    recurrence one position at a time from a zero state."""
    di, n, k, r = sizes(model)
    t = h.shape[0]
    az = h @ _f32(w["w_in"])
    a, z = az[:, :di], az[:, di:]
    padded = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), a])
    a = sum(padded[j:j + t] * _f32(w["conv_w"])[j] for j in range(k))
    a = jax.nn.silu(a + _f32(w["conv_b"]))
    dbc = a @ _f32(w["w_x"])
    delta, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = jax.nn.softplus(delta @ _f32(w["w_dt"]) + w["dt_bias"])  # [T, di]
    a_mat = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32),
                              (di, n))          # -exp(A_log), A_log = log 1..N

    def step(state, x):
        a_t, dt_t, b_t, c_t = x
        state = (jnp.exp(dt_t[:, None] * a_mat) * state
                 + (dt_t * a_t)[:, None] * b_t[None, :])
        return state, state @ c_t + a_t                        # D = 1

    _, m = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (a, dt, b, c))
    return (m * jax.nn.silu(z)) @ _f32(w["w_out"]), m


def lambda_init(layer):
    """``layer`` may be traced: one compiled layer of a kind serves every
    depth."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_attention(model, w, q, k, v, layer, window: int):
    """q [T, query heads, d], k and v [T, K/V heads, d] -> [T, query heads
    * d]; ``window`` 0: causal, else position t attends t-window+1 .. t.
    Every pair at once, a block of query positions at a time."""
    t, nq, d = q.shape
    per = nq // k.shape[1]            # query pairs that read one K/V pair
    lam_i = lambda_init(layer)
    lq1, lk1, lq2, lk2 = w["lam"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_i
    eps = float(model["layer_norm_eps"])
    # Query pair p = heads (2p, 2p+1); its K/V pair is p // per: the two
    # key heads, and the two value heads side by side.
    q = q.reshape(t, nq // 2, 2, d)
    k = jnp.repeat(k.reshape(t, -1, 2, d), per, axis=1)     # [T, pairs, 2, d]
    v = jnp.repeat(v.reshape(t, -1, 2 * d), per, axis=1)    # [T, pairs, 2d]
    key_pos = jnp.arange(t)[None, :]
    blocks = []
    for t0 in range(0, t, QUERY_BLOCK):
        q_pos = jnp.arange(t0, min(t0 + QUERY_BLOCK, t))[:, None]
        mask = key_pos <= q_pos
        if window:
            mask = mask & (key_pos > q_pos - window)
        scores = jnp.einsum("qped,kped->peqk", q[t0:t0 + QUERY_BLOCK], k)
        scores = jnp.where(mask, scores * d ** -0.5, -jnp.inf)
        o = jnp.einsum("peqk,kpf->qpef", jax.nn.softmax(scores, axis=-1), v)
        o = o[:, :, 0] - lam * o[:, :, 1]                   # [Q, pairs, 2d]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        blocks.append((o * (1.0 - lam_i)).reshape(o.shape[0], -1))  # gain 1
    return jnp.concatenate(blocks, axis=0)


def layer_forward(model, kind: str, w, x, mem, kv, layer):
    """One layer on one sequence: x [T, H] float32, ``mem`` the memory [T,
    inner] and ``kv`` layer F's (k, v) where they exist yet, ``layer`` the
    layer's index (traced).  Returns (x, what the layer made for later
    ones: an M layer its m, F its (k, v), else None)."""
    eps = float(model["layer_norm_eps"])
    t, hdim = x.shape
    d = hdim // model["num_attention_heads"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    h = layer_norm(x, w["ln1_b"], eps)
    made = None
    if kind == "M":
        out, made = mamba(model, w, h)
    elif kind == "G":
        out = (mem * jax.nn.silu(h @ _f32(w["w_g1"]))) @ _f32(w["w_g2"])
    else:
        if kind == "X":
            q = h @ _f32(w["w_q"]) + _f32(w["b_q"])
            k, v = kv
        else:
            qkv = h @ _f32(w["w_qkv"]) + _f32(w["b_qkv"])
            q = qkv[:, :nq * d]
            k = qkv[:, nq * d:(nq + nkv) * d].reshape(t, nkv, d)
            v = qkv[:, (nq + nkv) * d:].reshape(t, nkv, d)
            if kind == "F":
                made = (k, v)
        o = diff_attention(model, w, q.reshape(t, nq, d), k, v, layer,
                           model["sliding_window"] if kind == "W" else 0)
        out = o @ _f32(w["w_o"]) + _f32(w["b_o"])
    x = x + out
    gu = layer_norm(x, w["ln2_b"], eps) @ _f32(w["w1"])
    f = gu.shape[-1] // 2
    x = x + (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ _f32(w["w2"])
    return x, made


def logits(model: Dict[str, Any], weights: Dict[str, Any], tokens,
           keep) -> jax.Array:
    """Float32 logits [B, K, V] of a full forward pass over ``tokens``
    [B, S] (right-padded; padding never reaches an earlier position) at
    the positions ``keep`` [B, K].  One sequence at a time through one
    layer at a time."""
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_hidden_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not "
                         f"{model['num_hidden_layers']} layers of {KINDS!r}")
    eps = float(model["layer_norm_eps"])
    sharding = weights.get("sharding")
    dtype = _dtype(model)
    shape = (model["vocab_size"], model["hidden_size"])
    last_m = pattern.rindex("M")
    with jax.default_matmul_precision("highest"):
        table = jax.jit(lambda k: _table(k, *shape, dtype, EMBED_STD),
                        out_shardings=sharding)
        embed = table(weights["k_embed"])
        xs = [_f32(embed[tokens[b]]) for b in range(tokens.shape[0])]
        del embed                    # made again for the tied head
        mems = [None] * len(xs)
        kvs = [None] * len(xs)
        # One compiled maker and one compiled layer a KIND: the layer's
        # index is an argument.
        make = {kind: jax.jit(lambda k, kind=kind: make_layer(model, k, kind),
                              out_shardings=sharding) for kind in KINDS}
        run = {kind: jax.jit(lambda w, x, mem, kv, i, kind=kind:
                             layer_forward(model, kind, w, x, mem, kv, i))
               for kind in KINDS}
        for i, kind in enumerate(pattern):
            w = make[kind](weights["layer_keys"][i])
            for b in range(len(xs)):
                # A wait a layer a sequence: dispatch runs ahead of the
                # device, and the layers in flight would hold their
                # temporaries all at once.
                xs[b], made = jax.block_until_ready(run[kind](
                    w, xs[b], mems[b] if kind == "G" else None,
                    kvs[b] if kind == "X" else None, jnp.int32(i)))
                if kind == "F":
                    kvs[b] = made
                elif i == last_m:
                    mems[b] = made
            del w, made

        final_b = _normal(weights["k_final"], (shape[1],), dtype)
        kept = jnp.stack([layer_norm(x[keep[b]], final_b, eps)
                          for b, x in enumerate(xs)])
        rows = jax.jit(lambda a, h: a @ _f32(h).T)
        embed = table(weights["k_embed"])
        return jnp.concatenate(
            [rows(kept, embed[v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, shape[0], VOCAB_BLOCK)], axis=-1)
