"""Plain reference: the state-space / attention / routed-expert hybrid
decoder (NVIDIA-Nemotron-3-Nano-30B-A3B's block, ``nemotron_h``) in
float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the whole sequence goes through every layer; the state-space recurrence is
a plain ``scan`` over time from a zero state (no chunks, no matrix form,
nothing kept between calls); attention is the naive full causal softmax
(no cache); EVERY held expert is computed for every token and weighed by a
gate that is zero for the experts the token did not choose.  It takes
nothing from the program or the harness.

Each layer is ONE pre-norm mixer, ``x <- x + mixer(RMSNorm(x))``, by the
character of ``hybrid_override_pattern``:

    M   [z | xBC | dt] = x W_in                     (no bias)
        u_t  = silu(sum_j w_j xBC_{t-K+1+j} + b)    (depthwise, causal)
        u -> x [heads, P] | B [groups, N] | C [groups, N]
        dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)   (a head)
        S_t  = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[group of the head]
        y_t  = S_t C_t[group of the head] + D x_t
        out  = RMSNorm_by_group(y * silu(z)) * gain @ W_out
    *   q, k, v by heads (grouped-query), NO rotary embedding,
        softmax(q k / sqrt(head_dim), causal) v, W_o
    E   s = sigmoid(x W_r) in float32 over ALL the router's outputs; top-k
        of s + bias; weights s of the chosen / (their sum + 1e-20) * scale;
        sum over the chosen experts THIS share holds of
        W_2 relu(W_1 x)^2, plus the shared expert of the same form

The share: ``n_routed_experts`` experts from ``first_routed_expert`` on
are held, of ``router_outputs`` the router scores; an assignment to an
absent expert adds nothing (its rank of the expert-parallel pair adds
it).  With all of them held this is the published layer.

Departures from the published description (the configuration file lists
them under ``assumed``):
  * no rotary embedding in the attention layers, as the family's
    published code (``rope_theta`` and ``partial_rotary_factor`` are keys
    it does not read);
  * the state-space inner width is heads x head size (``expand`` goes
    unread);
  * the state ``S`` and its update are float32 (here everything is).

Weights come from the seed by the recipe of the program's
``models/hybrid_ssm.py``, written out again here: ``PRNGKey(seed)`` split
3 ways (embedding, head, layers), the layers' key split a layer, a layer's
key split 8 ways, an expert matrix's key split a ROUTER OUTPUT (the held
ones taken), a vocabulary table's key split a block of 4096 rows;
normal(0, 0.02) rounded to the model's dtype (the embedding normal(0,
1)), gains 1, the conv uniform in +-1/sqrt(taps), ``dt`` log-uniform in
[time_step_min, time_step_max] floored at time_step_floor and stored as
softplus's inverse, ``A`` uniform in [1, 16], ``D`` 1, router bias
normal(0, 0.01).  A layer's weights are made when the layer is run and
dropped after it.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02
ROUTER_BIAS_STD = 0.01
EMBED_STD = 1.0
EXPERT_BLOCK = 4        # experts widened to float32 and computed at a time
HEAD_BLOCK = 4          # query heads whose [S, S] scores are held at a time
TABLE_ROWS = 4096       # rows of a vocabulary table drawn at a time
VOCAB_BLOCK = 16384     # rows of the head widened to float32 at a time


# -- weights from the seed ---------------------------------------------------

def _dtype(model):
    return jnp.dtype(model.get("torch_dtype", "bfloat16"))


def _normal(key, shape, dtype, std=WEIGHT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _uniform(key, shape, dtype, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


def _table(key, rows, width, dtype, std=WEIGHT_STD):
    block = min(rows, TABLE_ROWS)
    keys = jax.random.split(key, rows // block)
    return jax.lax.map(lambda k: _normal(k, (block, width), dtype, std),
                       keys).reshape(rows, width)


def sizes(model):
    """(heads, head size, groups, state, taps, inner, conv channels)."""
    nh, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n, k = model["n_groups"], model["ssm_state_size"], \
        model["conv_kernel"]
    return nh, p, g, n, k, nh * p, nh * p + 2 * g * n


def make_layer(model, key, kind: str) -> Dict[str, Any]:
    dtype = _dtype(model)
    h = model["hidden_size"]
    ks = jax.random.split(key, 8)
    if kind == "M":
        nh, _, _, _, k, di, c = sizes(model)
        lo, hi = math.log(model["time_step_min"]), \
            math.log(model["time_step_max"])
        dt = jnp.exp(jax.random.uniform(ks[3], (nh,), jnp.float32)
                     * (hi - lo) + lo)
        dt = jnp.maximum(dt, model["time_step_floor"])
        return {"w_in": _normal(ks[0], (h, di + c + nh), dtype),
                "conv_w": _uniform(ks[1], (k, c), dtype, k ** -0.5),
                "conv_b": _uniform(ks[2], (c,), dtype, k ** -0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(ks[4], (nh,),
                                                    jnp.float32, 1.0, 16.0)),
                "w_out": _normal(ks[5], (di, h), dtype)}
    if kind == "*":
        d = model["head_dim"]
        nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
        return {"wq": _normal(ks[0], (h, nq * d), dtype),
                "wk": _normal(ks[1], (h, nkv * d), dtype),
                "wv": _normal(ks[2], (h, nkv * d), dtype),
                "wo": _normal(ks[3], (nq * d, h), dtype)}
    f, e = model["moe_intermediate_size"], model["router_outputs"]
    first = model.get("first_routed_expert", 0)
    held = slice(first, first + model["n_routed_experts"])
    fs = model["moe_shared_expert_intermediate_size"]

    def experts(key, shape):
        return jax.lax.map(lambda k: _normal(k, shape, dtype),
                           jax.random.split(key, e)[held])

    return {"router": _normal(ks[0], (h, e), dtype),
            "router_bias": ROUTER_BIAS_STD * jax.random.normal(
                ks[1], (e,), jnp.float32),
            "we_up": experts(ks[2], (h, f)),
            "we_down": experts(ks[3], (f, h)),
            "ws_up": _normal(ks[4], (h, fs), dtype),
            "ws_down": _normal(ks[5], (fs, h), dtype)}


def init_weights(model: Dict[str, Any], seed: int, sharding=None
                 ) -> Dict[str, Any]:
    """Keys only: the embedding's, the head's and one a layer; every
    array is made from its key when ``logits`` reaches it.  The seed is an
    argument of the compiled maker."""
    def make(seed):
        k_embed, k_head, k_layers = jax.random.split(
            jax.random.PRNGKey(seed), 3)
        return {"k_embed": k_embed, "k_head": k_head,
                "layer_keys": jax.random.split(
                    k_layers, model["num_hidden_layers"])}
    out = jax.jit(make, out_shardings=sharding)(jnp.int32(seed))
    out["sharding"] = sharding
    return out


# -- the three mixers -----------------------------------------------------------

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _f32(a):
    return a.astype(jnp.float32)


def mamba(model, w, x):
    """x [S, H] (normalised) -> [S, H]: the recurrence one position at a
    time from a zero state."""
    nh, p, g, n, k, di, c = sizes(model)
    s = x.shape[0]
    eps = float(model["norm_eps"])
    zxbcdt = x @ _f32(w["w_in"])
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + c], zxbcdt[:, di + c:]
    padded = jnp.concatenate([jnp.zeros((k - 1, c), jnp.float32), xbc])
    u = sum(padded[j:j + s] * _f32(w["conv_w"])[j] for j in range(k))
    u = jax.nn.silu(u + _f32(w["conv_b"]))
    xs = u[:, :di].reshape(s, nh, p)
    group = jnp.arange(nh) // (nh // g)              # a head's group
    b = u[:, di:di + g * n].reshape(s, g, n)[:, group]          # [S, nh, N]
    cm = u[:, di + g * n:].reshape(s, g, n)[:, group]
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # [S, nh]
    a = -jnp.exp(w["a_log"])

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + x_t   # D = 1

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32),
                        (xs, b, cm, dt))
    y = y.reshape(s, di) * jax.nn.silu(z)
    y = _rms(y.reshape(s, g, di // g), eps).reshape(s, di)      # gain 1
    return y @ _f32(w["w_out"])


def _attend_block(q, k, v, scale):
    """q [S, n, D] of one kv head's query heads, k/v [S, D]."""
    s = q.shape[0]
    scores = jnp.einsum("qnd,kd->nqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("nqk,kd->qnd", jax.nn.softmax(scores, -1), v)


def attention_layer(model, w, x, block=_attend_block):
    """x [S, H] (normalised) -> [S, H]; no rotary embedding."""
    s = x.shape[0]
    d = model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    per = nq // nkv
    q = (x @ _f32(w["wq"])).reshape(s, nq, d)
    k = (x @ _f32(w["wk"])).reshape(s, nkv, d)
    v = (x @ _f32(w["wv"])).reshape(s, nkv, d)
    outs = []
    for j in range(nkv):
        for h0 in range(j * per, (j + 1) * per, HEAD_BLOCK):
            outs.append(block(q[:, h0:min(h0 + HEAD_BLOCK, (j + 1) * per)],
                              k[:, j], v[:, j], d ** -0.5))
    return jnp.concatenate(outs, axis=1).reshape(s, nq * d) @ _f32(w["wo"])


def gates(model, w, x):
    """x [S, H] -> [S, held] float32: the weights of the chosen experts
    this share holds, zero for the rest.  The router scores ALL its
    outputs and normalises over all the chosen, whoever holds them; the
    bias enters the choice only."""
    k = model["num_experts_per_tok"]
    first = model.get("first_routed_expert", 0)
    s = jax.nn.sigmoid(x @ _f32(w["router"]))
    _, choice = jax.lax.top_k(s + w["router_bias"], k)
    chosen = jnp.take_along_axis(s, choice, axis=1)
    weight = chosen / (chosen.sum(1, keepdims=True) + 1e-20) \
        * float(model["routed_scaling_factor"])
    onehot = jax.nn.one_hot(choice, s.shape[1], dtype=jnp.float32)
    every = jnp.einsum("ske,sk->se", onehot, weight)
    return every[:, first:first + model["n_routed_experts"]]


def _relu2(x, up, down):
    a = jax.nn.relu(x @ _f32(up))
    return (a * a) @ _f32(down)


def _expert_block(x, up, down, g):
    """A block of experts [e, ...] for every token, weighed by g [S, e]."""
    a = jax.nn.relu(jnp.einsum("sh,ehf->esf", x, _f32(up)))
    y = jnp.einsum("esf,efh->esh", a * a, _f32(down))
    return jnp.einsum("esh,se->sh", y, g)


def experts_layer(model, w, x, block=_expert_block, shared=True):
    """Every held expert for every token, gated, EXPERT_BLOCK at a time;
    plus the shared one."""
    g = gates(model, w, x)
    out = jnp.zeros_like(x)
    for e0 in range(0, g.shape[1], EXPERT_BLOCK):
        eb = slice(e0, e0 + EXPERT_BLOCK)
        out = out + block(x, w["we_up"][eb], w["we_down"][eb], g[:, eb])
    if shared:
        out = out + _relu2(x, w["ws_up"], w["ws_down"])
    return out


def logits(model: Dict[str, Any], weights: Dict[str, Any], tokens,
           keep) -> jax.Array:
    """Float32 logits [B, K, V] of a full forward pass over ``tokens``
    [B, S] (right-padded; padding never reaches an earlier position) at
    the positions ``keep`` [B, K].  One sequence at a time through one
    layer at a time."""
    pattern = model["hybrid_override_pattern"]
    if len(pattern) != model["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern {pattern!r} is not "
                         f"{model['num_hidden_layers']} layers")
    eps = float(model["norm_eps"])
    sharding = weights.get("sharding")
    dtype = _dtype(model)
    shape = (model["vocab_size"], model["hidden_size"])
    with jax.default_matmul_precision("highest"):
        table = jax.jit(lambda k, std: _table(k, *shape, dtype, std),
                        out_shardings=sharding, static_argnums=1)
        make = {kind: jax.jit(lambda k, kind=kind: make_layer(model, k, kind),
                              out_shardings=sharding) for kind in "M*E"}

        # A wait after every block: dispatch runs ahead of the device, and
        # the blocks in flight would hold their temporaries all at once.
        def waited(fn):
            return lambda *a: jax.block_until_ready(fn(*a))
        attend = waited(jax.jit(_attend_block, static_argnums=3))
        block = waited(jax.jit(_expert_block))
        norm = jax.jit(lambda x: _rms(x, eps))
        ssm = waited(jax.jit(lambda w, x: mamba(model, w, x)))

        embed = table(weights["k_embed"], EMBED_STD)
        xs = [_f32(embed[tokens[b]]) for b in range(tokens.shape[0])]
        del embed
        for i, kind in enumerate(pattern):
            w = make[kind](weights["layer_keys"][i])
            for b in range(len(xs)):
                h = norm(xs[b])
                if kind == "M":
                    out = ssm(w, h)
                elif kind == "*":
                    out = attention_layer(model, w, h, attend)
                else:
                    out = experts_layer(model, w, h, block)
                xs[b] = xs[b] + out
            del w, out

        head = table(weights["k_head"], WEIGHT_STD)
        kept = jnp.stack([norm(x[keep[b]]) for b, x in enumerate(xs)])
        rows = jax.jit(lambda a, h: a @ _f32(h).T)
        return jnp.concatenate(
            [rows(kept, head[v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, shape[0], VOCAB_BLOCK)], axis=-1)
