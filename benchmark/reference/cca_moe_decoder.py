"""Plain reference: the compressed-convolutional-attention / top-1
routed-expert decoder (Zyphra's ZAYA1 block, ``zaya``) in float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the whole sequence goes through every layer, a layer's convolutions are
shifts of the whole sequence against a zero row (nothing kept between
calls, no chunks), attention is the naive full causal softmax (no
cache), EVERY expert is computed for every token and weighed by a gate
that is zero for the experts the token did not choose.  It takes nothing
from the program or the harness.

Each of ``num_hidden_layers`` layers is two pre-norm sublayers on one
residual, attention then experts, each merged by four vectors of the
hidden width:

    h <- (a_r * h + b_r) + (a_o * f(RMSNorm(h)) + b_o)

    attention (CCA, grouped form; Dq = heads x D, Dk = kv heads x D):
        q~_t = x_t W_q [Dq]    k~_t = x_t W_k [Dk]    u_t = [q~_t | k~_t]
        v_t  = [x_t W_v1 | x_{t-1} W_v2]           (x_{-1} = 0: the shift;
                                 the first half of the K/V heads take this
                                 token's values, the second half the
                                 token before's)
        c_t  = a_0 * u_{t-1} + a_1 * u_t + bias_0  (depthwise, u_{-1} = 0)
        d_t  = B_0 c_{t-1} + B_1 c_t + bias_1      (block-diagonal, a block
                                 of D x D a head, c_{-1} = 0)
        m^q_i = (q~_i + k~_{group of i}) / 2       (from the PRE-conv u)
        m^k_j = mean of m^q_i over group j's query heads
        q_i = d^q_i + m^q_i          k_j = d^k_j + m^k_j
        q_i <- sqrt(D) q_i / |q_i|   k_j <- tau_j sqrt(D) k_j / |k_j|
        rotary (half-split pairs) on the first partial_rotary_factor x D
        numbers of every head; softmax(q k / sqrt(D), causal) v; W_o

    experts (top-1, an MLP router with a carry through the depth):
        r^l_t = x_t W_r + gamma^l * r^{l-1}_t      (r^{-1} = 0; the state
                                 of the SAME token one layer up)
        z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r^l_t)))  (exact GELU)
        p = softmax(z) in float32;  e = the num_experts_per_tok largest of
        p + bias (the bias moves the choice only)
        y_t = sum over chosen e of p_e W^e_down(silu(x W^e_gate) * x W^e_up)
        (no renormalising, no scaling, no shared expert)

The head is the embedding transposed, after the final RMSNorm.

Weights come from the seed by the recipe of the program's
``models/hybrid_ssm.py`` for its ``CE`` pattern, written out again here:
``PRNGKey(seed)`` split 3 ways (embedding, [head: unused, tied], layers),
the layers' key split a SUBLAYER (2 a layer), a sublayer's key split 8
ways; matrices normal(0, 0.02) rounded to the model's dtype (the
embedding too), norm gains 1; the convolutions' taps and biases
uniform in +-1/sqrt(fan-in) (2, and 2 D); tau = 3 + 0.25 normal; the
router's MLP normal(0, 1/sqrt(width)), gamma = 0.5 + 0.1 normal, bias
normal(0, 0.01); an expert matrix's key split a ROUTER OUTPUT; the merge's
gains 1 + 0.1 normal and offsets 0.02 normal.  A layer's weights are made
when the layer is run and dropped after it.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02
ROUTER_BIAS_STD = 0.01
EMBED_STD = WEIGHT_STD
TAU_MEAN, TAU_STD = 3.0, 0.25
CARRY_MEAN, CARRY_STD = 0.5, 0.1
RES_GAIN_STD, RES_BIAS_STD = 0.1, 0.02
EXPERT_BLOCK = 2        # experts widened to float32 and computed at a time
HEAD_BLOCK = 4          # query heads whose [S, S] scores are held at a time
TABLE_ROWS = 4096       # most rows of a vocabulary table drawn at a time
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
    # The largest divisor of the rows that is at most TABLE_ROWS: 2732 for
    # 262 272.
    block = max(n for n in range(1, TABLE_ROWS + 1) if rows % n == 0)
    keys = jax.random.split(key, rows // block)
    return jax.lax.map(lambda k: _normal(k, (block, width), dtype, std),
                       keys).reshape(rows, width)


def sizes(model):
    """(query heads, K/V heads, head size, rotary numbers a head)."""
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // nq
    return nq, nkv, d, int(d * model["partial_rotary_factor"])


def _merge_vectors(key, h):
    draw = jax.random.normal(key, (4, h), jnp.float32)
    return (jnp.array([1.0, 0.0, 1.0, 0.0])[:, None]
            + jnp.array([RES_GAIN_STD, RES_BIAS_STD] * 2)[:, None] * draw)


def make_attention(model, key) -> Dict[str, Any]:
    dtype = _dtype(model)
    h = model["hidden_size"]
    nq, nkv, d, _ = sizes(model)
    c = (nq + nkv) * d
    ks = jax.random.split(key, 8)
    k_a, k_ab, k_b, k_bb = jax.random.split(ks[4], 4)
    return {"wq": _normal(ks[0], (h, nq * d), dtype),
            "wk": _normal(ks[1], (h, nkv * d), dtype),
            "wv": _normal(ks[2], (h, nkv * d), dtype),
            "wo": _normal(ks[3], (nq * d, h), dtype),
            "conv0_w": _uniform(k_a, (2, c), dtype, 2 ** -0.5),
            "conv0_b": _uniform(k_ab, (c,), dtype, 2 ** -0.5),
            "conv1_w": _uniform(k_b, (2, nq + nkv, d, d), dtype,
                                (2 * d) ** -0.5),
            "conv1_b": _uniform(k_bb, (c,), dtype, (2 * d) ** -0.5),
            "tau": TAU_MEAN + TAU_STD * jax.random.normal(ks[5], (nkv,),
                                                     jnp.float32),
            "res": _merge_vectors(ks[7], h)}


def make_experts(model, key) -> Dict[str, Any]:
    dtype = _dtype(model)
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    e, rh = model["num_experts"], model["router_hidden_size"]
    ks = jax.random.split(key, 8)
    k_gate, k_router = jax.random.split(ks[6])
    k_c, k_1, k_2, k_3 = jax.random.split(k_router, 4)

    def experts(key, shape):
        return jax.lax.map(lambda k: _normal(k, shape, dtype),
                           jax.random.split(key, e))

    return {"router": _normal(ks[0], (h, rh), dtype),
            "router_bias": ROUTER_BIAS_STD * jax.random.normal(
                ks[1], (e,), jnp.float32),
            "we_up": experts(ks[2], (h, f)),
            "we_down": experts(ks[3], (f, h)),
            "we_gate": experts(k_gate, (h, f)),
            "router_carry": CARRY_MEAN + CARRY_STD * jax.random.normal(
                k_c, (rh,), jnp.float32),
            "router_w1": _normal(k_1, (rh, rh), dtype, rh ** -0.5),
            "router_w2": _normal(k_2, (rh, rh), dtype, rh ** -0.5),
            "router_w3": _normal(k_3, (rh, e), dtype, rh ** -0.5),
            "res": _merge_vectors(ks[7], h)}


def init_weights(model: Dict[str, Any], seed: int, sharding=None
                 ) -> Dict[str, Any]:
    """Keys only: the embedding's and one a sublayer; every array is made
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


# -- the two sublayers -----------------------------------------------------------

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _f32(a):
    return a.astype(jnp.float32)


def _before(a):
    """Row t holds row t - 1 of ``a``; row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]])


def _rope(x, rot, theta):
    """x [S, heads, D]: half-split rotary pairs over the first ``rot``
    numbers of every head, position = row."""
    s = x.shape[0]
    freqs = theta ** (-jnp.arange(0, rot // 2, dtype=jnp.float32)
                      / (rot // 2))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(angles)[:, None], jnp.cos(angles)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def shifted_values(model, v):
    """v [S, Dk]: the first half of the K/V heads keep this token's
    values, the second half take the token before's."""
    _, nkv, d, _ = sizes(model)
    half = nkv // 2 * d
    return jnp.concatenate([v[:, :half], _before(v[:, half:])], axis=-1)


def qk_mean(model, lat):
    """lat [S, Nq + Nkv, D] the latents before the convolutions -> (a
    query head's mean with its group's key latent [S, Nq, D], a K/V
    head's mean of those over its group [S, Nkv, D])."""
    nq, nkv, d, _ = sizes(model)
    per = nq // nkv
    mean_q = 0.5 * (lat[:, :nq] + jnp.repeat(lat[:, nq:], per, axis=1))
    return mean_q, mean_q.reshape(-1, nkv, per, d).mean(axis=2)


def cca_qkv(model, w, x):
    """x [S, H] (normalised) -> q [S, Nq, D], k and v [S, Nkv, D] as
    attended."""
    nq, nkv, d, rot = sizes(model)
    s = x.shape[0]
    u = jnp.concatenate([x @ _f32(w["wq"]), x @ _f32(w["wk"])], axis=-1)
    v = shifted_values(model, x @ _f32(w["wv"]))
    a, b = _f32(w["conv0_w"]), _f32(w["conv1_w"])
    c = a[0] * _before(u) + a[1] * u + _f32(w["conv0_b"])
    heads = c.reshape(s, nq + nkv, d)
    conv = (jnp.einsum("sgi,gio->sgo", _before(heads), b[0])
            + jnp.einsum("sgi,gio->sgo", heads, b[1])
            + _f32(w["conv1_b"]).reshape(nq + nkv, d))
    mean_q, mean_k = qk_mean(model, u.reshape(s, nq + nkv, d))
    q, k = conv[:, :nq] + mean_q, conv[:, nq:] + mean_k

    def unit(a):
        norm = jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True))
        return a * (d ** 0.5 / jnp.maximum(norm, 1e-12))
    q, k = unit(q), unit(k) * w["tau"][:, None]
    theta = float(model["rope_parameters"]["hybrid"]["rope_theta"])
    return _rope(q, rot, theta), _rope(k, rot, theta), v.reshape(s, nkv, d)


def _attend_block(q, k, v, scale):
    """q [S, n, D] of one kv head's query heads, k/v [S, D]."""
    s = q.shape[0]
    scores = jnp.einsum("qnd,kd->nqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("nqk,kd->qnd", jax.nn.softmax(scores, -1), v)


def attention_layer(model, w, x, qkv, block=_attend_block):
    """x [S, H] (normalised) -> [S, H]."""
    nq, nkv, d, _ = sizes(model)
    per = nq // nkv
    q, k, v = qkv(w, x)
    outs = []
    for j in range(nkv):
        for h0 in range(j * per, (j + 1) * per, HEAD_BLOCK):
            outs.append(block(q[:, h0:min(h0 + HEAD_BLOCK, (j + 1) * per)],
                              k[:, j], v[:, j], d ** -0.5))
    return (jnp.concatenate(outs, axis=1).reshape(x.shape[0], nq * d)
            @ _f32(w["wo"]))


def gates(model, w, x, carry):
    """x [S, H], carry [S, R] -> (gate [S, E] float32: a chosen expert's
    probability, zero for the rest; the router's state [S, R])."""
    k = model["num_experts_per_tok"]
    r = x @ _f32(w["router"]) + w["router_carry"] * carry
    z = _rms(r, float(model["rms_norm_eps"]))                   # gain 1
    z = jax.nn.gelu(z @ _f32(w["router_w1"]), approximate=False)
    z = jax.nn.gelu(z @ _f32(w["router_w2"]), approximate=False)
    p = jax.nn.softmax(z @ _f32(w["router_w3"]), axis=-1)
    _, choice = jax.lax.top_k(p + w["router_bias"], k)
    onehot = jax.nn.one_hot(choice, p.shape[1], dtype=jnp.float32).sum(1)
    return onehot * p, r


def _expert_block(x, gate, up, down, g):
    """A block of experts [e, ...] for every token, weighed by g [S, e]."""
    a = jax.nn.silu(jnp.einsum("sh,ehf->esf", x, _f32(gate)))
    a = a * jnp.einsum("sh,ehf->esf", x, _f32(up))
    return jnp.einsum("esf,efh,se->sh", a, _f32(down), g)


def experts_layer(model, w, x, g, block=_expert_block):
    """Every expert for every token, gated, EXPERT_BLOCK at a time."""
    out = jnp.zeros_like(x)
    for e0 in range(0, g.shape[1], EXPERT_BLOCK):
        eb = slice(e0, e0 + EXPERT_BLOCK)
        out = out + block(x, w["we_gate"][eb], w["we_up"][eb],
                          w["we_down"][eb], g[:, eb])
    return out


def _merge(w, x, out):
    a_r, b_r, a_o, b_o = w["res"]
    return (a_r * x + b_r) + (a_o * out + b_o)


def logits(model: Dict[str, Any], weights: Dict[str, Any], tokens,
           keep) -> jax.Array:
    """Float32 logits [B, K, V] of a full forward pass over ``tokens``
    [B, S] (right-padded; padding never reaches an earlier position) at
    the positions ``keep`` [B, K].  One sequence at a time through one
    sublayer at a time."""
    eps = float(model["rms_norm_eps"])
    sharding = weights.get("sharding")
    dtype = _dtype(model)
    shape = (model["vocab_size"], model["hidden_size"])
    rh = model["router_hidden_size"]
    with jax.default_matmul_precision("highest"):
        table = jax.jit(lambda k: _table(k, *shape, dtype, EMBED_STD),
                        out_shardings=sharding)
        make_a = jax.jit(lambda k: make_attention(model, k),
                         out_shardings=sharding)
        make_e = jax.jit(lambda k: make_experts(model, k),
                         out_shardings=sharding)

        # A wait after every block: dispatch runs ahead of the device, and
        # the blocks in flight would hold their temporaries all at once.
        def waited(fn):
            return lambda *a: jax.block_until_ready(fn(*a))
        attend = waited(jax.jit(_attend_block, static_argnums=3))
        block = waited(jax.jit(_expert_block))
        norm = jax.jit(lambda x: _rms(x, eps))
        qkv = waited(jax.jit(lambda w, x: cca_qkv(model, w, x)))
        route = jax.jit(lambda w, x, c: gates(model, w, x, c))
        merge = jax.jit(_merge)

        embed = table(weights["k_embed"])
        xs = [_f32(embed[tokens[b]]) for b in range(tokens.shape[0])]
        carries = [jnp.zeros((x.shape[0], rh), jnp.float32) for x in xs]
        for i in range(model["num_hidden_layers"]):
            w = make_a(weights["layer_keys"][2 * i])
            for b in range(len(xs)):
                out = attention_layer(model, w, norm(xs[b]), qkv, attend)
                xs[b] = merge(w, xs[b], out)
            w = make_e(weights["layer_keys"][2 * i + 1])
            for b in range(len(xs)):
                h = norm(xs[b])
                g, carries[b] = route(w, h, carries[b])
                xs[b] = merge(w, xs[b], experts_layer(model, w, h, g, block))
            del w, out

        kept = jnp.stack([norm(x[keep[b]]) for b, x in enumerate(xs)])
        rows = jax.jit(lambda a, h: a @ _f32(h).T)
        return jnp.concatenate(
            [rows(kept, embed[v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, shape[0], VOCAB_BLOCK)], axis=-1)
