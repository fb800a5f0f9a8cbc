"""Plain reference: the delta-rule linear attention / latent attention /
routed-expert decoder (Kimi-Linear-48B-A3B's block, ``kimi_linear``) in
float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the whole sequence goes through every layer; the linear-attention
recurrence is a plain ``scan`` over time from a zero state, one token a
step as it is written below (no chunk form, nothing kept between calls);
latent attention up-projects every position's keys and values by heads and
is the naive full causal softmax (no absorption, no cache); EVERY held
expert is computed for every token and weighed by a gate that is zero for
the experts the token did not choose.  It takes nothing from the program
or the harness.

A layer is a mixer and then a feed-forward, each pre-norm on the one
residual, ``x <- x + f(RMSNorm(x))``.  Layer ``l`` (from 1) mixes by latent
attention where ``linear_attn_config.full_attn_layers`` lists it and by
Kimi Delta Attention where ``kda_layers`` does; its feed-forward is a dense
gated MLP for the first ``first_k_dense_replace`` layers and experts after.

    KDA   q, k, v = W_q x, W_k x, W_v x, each through its own causal
          depthwise conv of ``short_conv_kernel_size`` taps (no bias) and
          silu; per head of ``head_dim`` numbers
          q_t = l2norm(q_t) * head_dim^-1/2;  k_t = l2norm(k_t)
          g_t = -exp(A_log[h]) softplus(W_fb (W_fa x_t) + dt_bias)   (a
                log-decay a CHANNEL of the head's keys, <= 0)
          beta_t = sigmoid(w_b[h] . x_t)
          S'  = Diag(exp g_t) S_{t-1}                  (S [d_k, d_v])
          S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
          o_t = S_t^T q_t
          out = W_o [RMSNorm_a_head(o_t) * gain * sigmoid(W_gb (W_ga x_t))]
    MLA   q = W_q x by heads of qk_nope_head_dim + qk_rope_head_dim;
          [c | k_pe] = W_kva x; c <- RMSNorm(c); [k_nope | v] = W_kvb c by
          heads; a head's key is [k_nope | k_pe], k_pe shared by all;
          softmax(q k / sqrt(qk_nope + qk_rope), causal) v; W_o.  NO rotary
          embedding on any number (``mla_use_nope``).
    MoE   s = sigmoid(x W_r) in float32 over ALL the router's outputs; top-k
          of s + bias (the bias moves the choice only; one group, so the
          grouped choice is the plain one); weights s of the chosen /
          (their sum + 1e-20) where ``moe_renormalize``, times
          ``routed_scaling_factor``; sum over the chosen experts THIS share
          holds of W_down(silu(W_gate x) * W_up x), plus
          ``num_shared_experts`` shared ones of the same form and width.

The share: ``num_experts`` experts from ``first_routed_expert`` on are
held, of ``router_outputs`` the router scores; an assignment to an absent
expert adds nothing (its rank of the expert-parallel group adds it).  With
all of them held this is the published layer.  ``vocab_size`` rows of the
embedding and of the head are held: a smaller vocabulary.

Departures from the published description, and what the configuration
lists under ``assumed``: the convs and the low-rank pairs carry no bias;
``head_dim^-1/2`` is applied to q after its l2 norm; the l2 norm is ``x *
rsqrt(sum x^2 + 1e-6)``; the output norm's epsilon is ``rms_norm_eps``;
``S`` and its update are float32 (here everything is).

Weights come from the seed by the recipe of the program's
``models/hybrid_ssm.py``, written out again here: ``PRNGKey(seed)`` split 3
ways (embedding, head, layers), the layers' key split a SUBLAYER (two a
layer: mixer, feed-forward), a sublayer's key split 8 ways and some of
those again as ``make_sublayer`` shows, an expert matrix's key split a
ROUTER OUTPUT (the held ones taken), a vocabulary table's key split a block
of 4096 rows; normal(0, 0.02) rounded to the model's dtype (the embedding
normal(0, 1)), gains 1, the convs uniform in +-1/sqrt(taps), ``dt``
log-uniform in [0.001, 0.1] floored at 1e-4 and stored as softplus's
inverse, ``A`` uniform in [1, 16] a head, router bias normal(0, 0.01).  A
sublayer's weights are made when it is run and dropped after it.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02
ROUTER_BIAS_STD = 0.01
EMBED_STD = 1.0
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4
L2_EPS = 1e-6
EXPERT_BLOCK = 4        # experts widened to float32 and computed at a time
HEAD_BLOCK = 4          # heads whose [S, S] scores are held at a time
TABLE_ROWS = 4096       # rows of a vocabulary table drawn at a time
VOCAB_BLOCK = 16384     # rows of the head widened to float32 at a time


def pattern(model: Dict[str, Any]) -> str:
    """One character a SUBLAYER, two a layer: ``K`` or ``L`` the mixer,
    ``-`` or ``E`` the feed-forward."""
    lin = model["linear_attn_config"]
    out = []
    for layer in range(1, model["num_hidden_layers"] + 1):
        if (layer in lin["full_attn_layers"]) == (layer in lin["kda_layers"]):
            raise ValueError(f"layer {layer} has to be in ONE of "
                             f"linear_attn_config's kda_layers and "
                             f"full_attn_layers")
        out.append("L" if layer in lin["full_attn_layers"] else "K")
        out.append("-" if layer <= model["first_k_dense_replace"] else "E")
    return "".join(out)


# -- weights from the seed ---------------------------------------------------

def _dtype(model):
    return jnp.dtype(model.get("torch_dtype", "bfloat16"))


def _normal(key, shape, dtype, std=WEIGHT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _uniform(key, shape, dtype, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


def _table(key, rows, width, dtype, std=WEIGHT_STD):
    block = max(n for n in range(1, TABLE_ROWS + 1) if rows % n == 0)
    keys = jax.random.split(key, rows // block)
    return jax.lax.map(lambda k: _normal(k, (block, width), dtype, std),
                       keys).reshape(rows, width)


def kda_sizes(model):
    """(heads, head size, taps, inner width)."""
    lin = model["linear_attn_config"]
    nh, d = lin["num_heads"], lin["head_dim"]
    return nh, d, lin["short_conv_kernel_size"], nh * d


def mla_sizes(model):
    """(heads, latent, nope, rope, value) widths."""
    return (model["num_attention_heads"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"])


def make_sublayer(model, key, kind: str) -> Dict[str, Any]:
    dtype = _dtype(model)
    h = model["hidden_size"]
    ks = jax.random.split(key, 8)
    if kind == "K":
        nh, d, k, di = kda_sizes(model)
        k_fa, k_fb, k_ga, k_gb, k_beta, k_conv = jax.random.split(ks[4], 6)
        dt = jnp.exp(jax.random.uniform(ks[5], (di,), jnp.float32)
                     * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        dt = jnp.maximum(dt, DT_FLOOR)
        return {"wq": _normal(ks[0], (h, di), dtype),
                "wk": _normal(ks[1], (h, di), dtype),
                "wv": _normal(ks[2], (h, di), dtype),
                "wo": _normal(ks[3], (di, h), dtype),
                # The three convs' taps, q | k | v channels side by side.
                "conv_w": _uniform(k_conv, (k, 3 * di), dtype, k ** -0.5),
                "w_fa": _normal(k_fa, (h, d), dtype),
                "w_fb": _normal(k_fb, (d, di), dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(ks[6], (nh,),
                                                    jnp.float32, 1.0, 16.0)),
                "w_beta": _normal(k_beta, (h, nh), dtype),
                "w_ga": _normal(k_ga, (h, d), dtype),
                "w_gb": _normal(k_gb, (d, di), dtype)}
    if kind == "L":
        nh, dc, dn, dr, dv = mla_sizes(model)
        return {"wq": _normal(ks[0], (h, nh * (dn + dr)), dtype),
                "w_kva": _normal(ks[1], (h, dc + dr), dtype),
                "w_kvb": _normal(ks[2], (dc, nh * (dn + dv)), dtype),
                "wo": _normal(ks[3], (nh * dv, h), dtype)}
    if kind == "-":
        f = model["intermediate_size"]
        return {"w_gate": _normal(ks[0], (h, f), dtype),
                "w_up": _normal(ks[1], (h, f), dtype),
                "w_down": _normal(ks[2], (f, h), dtype)}
    f, e = model["moe_intermediate_size"], model["router_outputs"]
    first = model.get("first_routed_expert", 0)
    held = slice(first, first + model["num_experts"])
    fs = f * model["num_shared_experts"]
    k_gate, _ = jax.random.split(ks[6])

    def experts(key, shape):
        return jax.lax.map(lambda k: _normal(k, shape, dtype),
                           jax.random.split(key, e)[held])

    w = {"router": _normal(ks[0], (h, e), dtype),
         "router_bias": ROUTER_BIAS_STD * jax.random.normal(
             ks[1], (e,), jnp.float32),
         "we_gate": experts(k_gate, (h, f)),
         "we_up": experts(ks[2], (h, f)),
         "we_down": experts(ks[3], (f, h))}
    if fs:
        w.update(ws_gate=_normal(ks[7], (h, fs), dtype),
                 ws_up=_normal(ks[4], (h, fs), dtype),
                 ws_down=_normal(ks[5], (fs, h), dtype))
    return w


def init_weights(model: Dict[str, Any], seed: int, sharding=None
                 ) -> Dict[str, Any]:
    """Keys only: the embedding's, the head's and one a sublayer; every
    array is made from its key when ``logits`` reaches it.  The seed is an
    argument of the compiled maker."""
    def make(seed):
        k_embed, k_head, k_layers = jax.random.split(
            jax.random.PRNGKey(seed), 3)
        return {"k_embed": k_embed, "k_head": k_head,
                "layer_keys": jax.random.split(k_layers,
                                               len(pattern(model)))}
    out = jax.jit(make, out_shardings=sharding)(jnp.int32(seed))
    out["sharding"] = sharding
    return out


# -- the sublayers -----------------------------------------------------------

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _f32(a):
    return a.astype(jnp.float32)


def short_conv(x, taps, which: str):
    """x [S, C] through a causal depthwise conv (zeros before the first
    position) and silu; ``which`` names whose it is, "q", "k" or "v"."""
    s, k = x.shape[0], taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(padded[j:j + s] * taps[j] for j in range(k)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def log_decay(model, w, x):
    """x [S, H] -> g [S, heads, d], <= 0: a log-decay a channel."""
    nh, d, _, _ = kda_sizes(model)
    f = (x @ _f32(w["w_fa"])) @ _f32(w["w_fb"])
    return (-jnp.exp(w["a_log"])[:, None]
            * jax.nn.softplus(f + w["dt_bias"]).reshape(-1, nh, d))


def beta_of(model, w, x):
    """x [S, H] -> beta [S, heads]."""
    return jax.nn.sigmoid(x @ _f32(w["w_beta"]))


def output_gate(model, w, x):
    """x [S, H] -> [S, inner], the gate of the mixer's output."""
    return jax.nn.sigmoid((x @ _f32(w["w_ga"])) @ _f32(w["w_gb"]))


def head_norm(o, eps):
    """o [S, heads, d]: an RMSNorm a head (the gain is 1)."""
    return _rms(o, eps)


def kda(model, w, x):
    """x [S, H] (normalised) -> [S, H]: the recurrence one position at a
    time from a zero state."""
    nh, d, _, di = kda_sizes(model)
    s = x.shape[0]
    taps = _f32(w["conv_w"])
    q, k, v = (short_conv(x @ _f32(w[name]), taps[:, j * di:(j + 1) * di],
                          name[1]).reshape(s, nh, d)
               for j, name in enumerate(("wq", "wk", "wv")))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g, beta = log_decay(model, w, x), beta_of(model, w, x)

    def step(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = jnp.exp(g_t)[:, :, None] * state             # [h, d_k, d_v]
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + jnp.einsum("hk,hv->hkv", b_t[:, None] * k_t,
                                   v_t - read)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = head_norm(o, float(model["rms_norm_eps"])).reshape(s, di)
    return (o * output_gate(model, w, x)) @ _f32(w["wo"])


def latent_qkv(model, w, x):
    """x [S, H] -> (q [S, heads, nope + rope], k_nope [S, heads, nope],
    k_pe [S, rope] shared by the heads, v [S, heads, value])."""
    nh, dc, dn, dr, dv = mla_sizes(model)
    s = x.shape[0]
    q = (x @ _f32(w["wq"])).reshape(s, nh, dn + dr)
    kva = x @ _f32(w["w_kva"])
    c = _rms(kva[:, :dc], float(model["rms_norm_eps"]))          # gain 1
    kvb = (c @ _f32(w["w_kvb"])).reshape(s, nh, dn + dv)
    return q, kvb[..., :dn], kva[:, dc:], kvb[..., dn:]


def _attend_block(q, k, v, scale):
    """q, k [S, n, D], v [S, n, Dv] of a block of heads."""
    s = q.shape[0]
    scores = jnp.einsum("qnd,knd->nqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)


def latent_attention(model, w, x, block=_attend_block):
    """x [S, H] (normalised) -> [S, H]; no rotary embedding."""
    nh, _, dn, dr, dv = mla_sizes(model)
    s = x.shape[0]
    q, k_nope, k_pe, v = latent_qkv(model, w, x)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (s, nh, dr))], axis=-1)
    outs = [block(q[:, h0:h0 + HEAD_BLOCK], k[:, h0:h0 + HEAD_BLOCK],
                  v[:, h0:h0 + HEAD_BLOCK], (dn + dr) ** -0.5)
            for h0 in range(0, nh, HEAD_BLOCK)]
    return jnp.concatenate(outs, axis=1).reshape(s, nh * dv) @ _f32(w["wo"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def mlp(model, w, x):
    return _swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


def gates(model, w, x):
    """x [S, H] -> [S, held] float32: the weights of the chosen experts
    this share holds, zero for the rest.  The router scores ALL its
    outputs and normalises over all the chosen, whoever holds them; the
    bias enters the choice only."""
    k = model["num_experts_per_token"]
    first = model.get("first_routed_expert", 0)
    s = jax.nn.sigmoid(x @ _f32(w["router"]))
    _, choice = jax.lax.top_k(s + w["router_bias"], k)
    weight = jnp.take_along_axis(s, choice, axis=1)
    if model["moe_renormalize"]:
        weight = weight / (weight.sum(1, keepdims=True) + 1e-20)
    weight = weight * float(model["routed_scaling_factor"])
    onehot = jax.nn.one_hot(choice, s.shape[1], dtype=jnp.float32)
    every = jnp.einsum("ske,sk->se", onehot, weight)
    return every[:, first:first + model["num_experts"]]


def _expert_block(x, gate, up, down, g):
    """A block of experts [e, ...] for every token, weighed by g [S, e]."""
    a = (jax.nn.silu(jnp.einsum("sh,ehf->esf", x, _f32(gate)))
         * jnp.einsum("sh,ehf->esf", x, _f32(up)))
    y = jnp.einsum("esf,efh->esh", a, _f32(down))
    return jnp.einsum("esh,se->sh", y, g)


def experts_layer(model, w, x, block=_expert_block):
    """Every held expert for every token, gated, EXPERT_BLOCK at a time;
    plus the shared ones."""
    g = gates(model, w, x)
    out = jnp.zeros_like(x)
    for e0 in range(0, g.shape[1], EXPERT_BLOCK):
        eb = slice(e0, e0 + EXPERT_BLOCK)
        out = out + block(x, w["we_gate"][eb], w["we_up"][eb],
                          w["we_down"][eb], g[:, eb])
    if "ws_gate" in w:
        out = out + _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out


def logits(model: Dict[str, Any], weights: Dict[str, Any], tokens,
           keep) -> jax.Array:
    """Float32 logits [B, K, V] of a full forward pass over ``tokens``
    [B, S] (right-padded; padding never reaches an earlier position) at
    the positions ``keep`` [B, K].  One sequence at a time through one
    sublayer at a time."""
    kinds = pattern(model)
    eps = float(model["rms_norm_eps"])
    sharding = weights.get("sharding")
    dtype = _dtype(model)
    shape = (model["vocab_size"], model["hidden_size"])
    with jax.default_matmul_precision("highest"):
        table = jax.jit(lambda k, std: _table(k, *shape, dtype, std),
                        out_shardings=sharding, static_argnums=1)
        make = {kind: jax.jit(
            lambda k, kind=kind: make_sublayer(model, k, kind),
            out_shardings=sharding) for kind in set(kinds)}

        # A wait after every block: dispatch runs ahead of the device, and
        # the blocks in flight would hold their temporaries all at once.
        def waited(fn):
            return lambda *a: jax.block_until_ready(fn(*a))
        attend = waited(jax.jit(_attend_block, static_argnums=3))
        block = waited(jax.jit(_expert_block))
        norm = jax.jit(lambda x: _rms(x, eps))
        run = {"K": waited(jax.jit(lambda w, x: kda(model, w, x))),
               "L": lambda w, x: latent_attention(model, w, x, attend),
               "-": waited(jax.jit(lambda w, x: mlp(model, w, x))),
               "E": lambda w, x: experts_layer(model, w, x, block)}

        embed = table(weights["k_embed"], EMBED_STD)
        xs = [_f32(embed[tokens[b]]) for b in range(tokens.shape[0])]
        del embed
        for i, kind in enumerate(kinds):
            w = make[kind](weights["layer_keys"][i])
            for b in range(len(xs)):
                xs[b] = xs[b] + run[kind](w, norm(xs[b]))
            del w

        head = table(weights["k_head"], WEIGHT_STD)
        kept = jnp.stack([norm(x[keep[b]]) for b, x in enumerate(xs)])
        rows = jax.jit(lambda a, h: a @ _f32(h).T)
        return jnp.concatenate(
            [rows(kept, head[v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, shape[0], VOCAB_BLOCK)], axis=-1)
