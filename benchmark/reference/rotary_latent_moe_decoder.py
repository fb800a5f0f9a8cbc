"""Plain reference: the rotary latent attention / routed-expert decoder
(sarvam-105b's block, ``sarvam_mla``) in float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the whole sequence goes through every layer; latent attention up-projects
EVERY position's keys and values by heads and is the naive full causal
softmax (no absorption, no cache, nothing kept between calls); EVERY held
expert is computed for every token and weighed by a gate that is zero for
the experts the token did not choose.  It takes nothing from the program
or the harness.

A layer is latent attention and then a feed-forward, each pre-norm on the
one plain residual, ``x <- x + f(RMSNorm(x))``; the feed-forward is a dense
gated MLP of ``intermediate_size`` for the first ``first_k_dense_replace``
layers and experts after; a final RMSNorm; an output head of its own.

    MLA   q = W_q x by heads of qk_nope_head_dim + qk_rope_head_dim (ONE
          matrix: no ``q_lora_rank``);  [c | k_r] = W_kva x;
          c <- RMSNorm(c) (``use_qk_norm``: the norm over the
          ``kv_lora_rank`` latent numbers, the gain 1);
          [k_nope | v] = W_kvb c by heads; a head's key is [k_nope | k_r],
          k_r shared by all heads.  q's last qk_rope_head_dim numbers a
          head and k_r are ROTATED at their position p (rotate-half: pair
          i with i + D/2) by the angle p f_i, the sin and the cos times
          ``m(mscale) / m(mscale_all_dim)`` with ``m(s) = 0.1 s ln(factor)
          + 1``.  ``deepseek_yarn``: f_i blends the plain frequency
          ``theta^(-2i/D)`` and the same over ``factor``, by a linear ramp
          between the pairs that turn ``beta_fast`` and ``beta_slow`` times
          in ``original_max_position_embeddings`` positions.
          softmax(q k * (qk_nope + qk_rope)^-1/2 * m(mscale_all_dim)^2,
          causal) v;  W_o.
    MoE   s = sigmoid(x W_r) in float32 over ALL the router's outputs;
          top-k of s + bias (``moe_router_enable_expert_bias``: the bias
          moves the choice only; no groups); weights s of the chosen /
          (their sum + 1e-20), times ``routed_scaling_factor``; sum over
          the chosen experts THIS share holds of W_down(silu(W_gate x) *
          W_up x), plus ``num_shared_experts`` shared ones of the same
          form and width for every token.

The share: ``num_experts`` experts from ``first_routed_expert`` on are
held, of ``router_outputs`` the router scores; an assignment to an absent
expert adds nothing (its rank of the expert-parallel group adds it).  With
all of them held this is the published layer.  ``vocab_size`` rows of the
embedding and of the head are held: a smaller vocabulary.

What the configuration lists under ``assumed``: ``use_qk_norm`` read as
the latent's norm above and no norm a head; sigmoid scores, renormalised;
rotate-half pairs.

Weights come from the seed by the recipe of the program's
``models/hybrid_ssm.py``, written out again here: ``PRNGKey(seed)`` split 3
ways (embedding, head, layers), the layers' key split a SUBLAYER (two a
layer: attention, feed-forward), a sublayer's key split 8 ways and one of
those again as ``make_sublayer`` shows, an expert matrix's key split a
ROUTER OUTPUT (the held ones taken), a vocabulary table's key split a block
of 4096 rows; normal(0, 0.02) rounded to the model's dtype (the embedding
normal(0, 1)), gains 1, router bias normal(0, 0.01).  A sublayer's weights
are made when it is run and dropped after it, the routed experts'
``EXPERT_BLOCK`` experts at a time; attention holds ``HEAD_BLOCK`` heads'
scores of ``QUERY_BLOCK`` query rows at a time, the experts a block of
``TOKEN_BLOCK`` tokens, the head ``VOCAB_BLOCK`` rows: at the published
widths and 16 k positions it fits beside an engine that fills two thirds
of the chip.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02
ROUTER_BIAS_STD = 0.01
EMBED_STD = 1.0
EXPERT_BLOCK = 4        # experts made, widened to float32 and run at a time
TOKEN_BLOCK = 2048      # tokens an MLP or a block of experts takes at a time
HEAD_BLOCK = 8          # heads whose scores are held at a time
QUERY_BLOCK = 256       # query rows whose scores are held at a time
TABLE_ROWS = 4096       # rows of a vocabulary table drawn at a time
VOCAB_BLOCK = 16384     # rows of the head widened to float32 at a time


def pattern(model: Dict[str, Any]) -> str:
    """One character a SUBLAYER, two a layer: ``L`` the mixer, ``-`` or
    ``E`` the feed-forward."""
    return "".join("L" + ("-" if layer <= model["first_k_dense_replace"]
                          else "E")
                   for layer in range(1, model["num_hidden_layers"] + 1))


# -- weights from the seed ---------------------------------------------------

def _dtype(model):
    return jnp.dtype(model.get("torch_dtype", "bfloat16"))


def _normal(key, shape, dtype, std=WEIGHT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _table(key, rows, width, dtype, std=WEIGHT_STD):
    block = max(n for n in range(1, TABLE_ROWS + 1) if rows % n == 0)
    keys = jax.random.split(key, rows // block)
    return jax.lax.map(lambda k: _normal(k, (block, width), dtype, std),
                       keys).reshape(rows, width)


def mla_sizes(model):
    """(heads, latent, nope, rope, value) widths."""
    return (model["num_attention_heads"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"])


def make_sublayer(model, key, kind: str) -> Dict[str, Any]:
    """What a sublayer holds but its routed experts' matrices: an ``E``
    keeps their three keys (``make_experts``)."""
    dtype = _dtype(model)
    h = model["hidden_size"]
    ks = jax.random.split(key, 8)
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
    e = model["router_outputs"]
    fs = model["moe_intermediate_size"] * model["num_shared_experts"]
    k_gate, _ = jax.random.split(ks[6])
    w = {"router": _normal(ks[0], (h, e), dtype),
         "router_bias": ROUTER_BIAS_STD * jax.random.normal(
             ks[1], (e,), jnp.float32),
         "k_gate": k_gate, "k_up": ks[2], "k_down": ks[3]}
    if fs:
        w.update(ws_gate=_normal(ks[7], (h, fs), dtype),
                 ws_up=_normal(ks[4], (h, fs), dtype),
                 ws_down=_normal(ks[5], (fs, h), dtype))
    return w


def make_experts(model, w, e0: int, n: int):
    """(gate, up, down) of held experts ``e0`` .. ``e0 + n`` of this
    share: a key a ROUTER OUTPUT, so an expert's matrix is the same
    whichever share holds it."""
    dtype = _dtype(model)
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    first = model.get("first_routed_expert", 0) + e0

    def stack(key, shape):
        keys = jax.random.split(key, model["router_outputs"])
        return jax.lax.map(lambda k: _normal(k, shape, dtype),
                           jax.lax.dynamic_slice_in_dim(keys, first, n))
    return (stack(w["k_gate"], (h, f)), stack(w["k_up"], (h, f)),
            stack(w["k_down"], (f, h)))


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


def yarn_inv_freq(model):
    """[qk_rope_head_dim / 2]: each pair's plain inverse frequency blended
    with the one divided by ``factor``."""
    dim, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    rs = model["rope_scaling"]
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    orig = rs["original_max_position_embeddings"]

    def pair_at(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair_at(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_at(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain / float(rs["factor"]) * ramp + plain * (1.0 - ramp)


def magnitude(model, mscale: float) -> float:
    """YaRN's ``m``: 0.1 mscale ln(factor) + 1."""
    return 0.1 * mscale * math.log(float(model["rope_scaling"]["factor"])) \
        + 1.0


def softmax_scale(model) -> float:
    m = magnitude(model, float(model["rope_scaling"]["mscale_all_dim"]))
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def rotate(model, x):
    """x [S, N, D] at positions 0..S-1, pairs (i, i + D/2)."""
    rs = model["rope_scaling"]
    mag = (magnitude(model, float(rs["mscale"]))
           / magnitude(model, float(rs["mscale_all_dim"])))
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(model)
    sin, cos = jnp.sin(ang)[:, None] * mag, jnp.cos(ang)[:, None] * mag
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_norm(model, c):
    """The norm over the ``kv_lora_rank`` latent numbers (the gain 1)."""
    return _rms(c, float(model["rms_norm_eps"]))


def latent_row(model, w, x):
    """x [S, H] -> (c [S, latent] normalised, k_r [S, rope] rotated): what
    a position keeps for every later one."""
    dc = model["kv_lora_rank"]
    kva = x @ _f32(w["w_kva"])
    return latent_norm(model, kva[:, :dc]), rotate(model, kva[:, None,
                                                              dc:])[:, 0]


def head_block(model, w, x, c, k_r, h0, n: int):
    """(q, k [S, n, nope + rope], v [S, n, value]) of heads ``h0`` .. ``h0
    + n``, every position's keys and values up-projected."""
    nh, dc, dn, dr, dv = mla_sizes(model)
    s = x.shape[0]

    def of_heads(m, width):
        """[in, heads * width] -> these heads' [in, n * width] float32."""
        m = m.reshape(m.shape[0], nh, width)
        return _f32(jax.lax.dynamic_slice_in_dim(m, h0, n, axis=1)).reshape(
            m.shape[0], n * width)
    q = (x @ of_heads(w["wq"], dn + dr)).reshape(s, n, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(model, q[..., dn:])], -1)
    kvb = (c @ of_heads(w["w_kvb"], dn + dv)).reshape(s, n, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_r[:, None], (s, n, dr))], -1)
    return q, k, kvb[..., dn:]


def _attend_block(q, k, v, q0, scale):
    """Query rows ``q0`` .. of a block of heads against every key: q [Q,
    n, D], k [S, n, D], v [S, n, Dv] -> [Q, n, Dv]."""
    scores = jnp.einsum("qnd,knd->nqk", q, k) * scale
    rows = q0 + jnp.arange(q.shape[0])
    causal = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)


def latent_attention(model, w, x, heads=head_block, block=_attend_block):
    """x [S, H] (normalised) -> [S, H]."""
    nh, dv = model["num_attention_heads"], model["v_head_dim"]
    s = x.shape[0]
    c, k_r = latent_row(model, w, x)
    outs = []
    for h0 in range(0, nh, HEAD_BLOCK):
        q, k, v = heads(model, w, x, c, k_r, h0, min(HEAD_BLOCK, nh - h0))
        outs.append(jnp.concatenate(
            [block(q[q0:q0 + QUERY_BLOCK], k, v, q0, softmax_scale(model))
             for q0 in range(0, s, QUERY_BLOCK)]))
    return jnp.concatenate(outs, axis=1).reshape(s, nh * dv) @ _f32(w["wo"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def by_token_blocks(fn, *rows):
    """``fn`` over blocks of ``TOKEN_BLOCK`` rows of each of ``rows``."""
    return jnp.concatenate(
        [fn(*(a[t0:t0 + TOKEN_BLOCK] for a in rows))
         for t0 in range(0, rows[0].shape[0], TOKEN_BLOCK)])


def mlp(model, w, x):
    return _swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


def gates(model, w, x):
    """x [S, H] -> [S, held] float32: the weights of the chosen experts
    this share holds, zero for the rest.  The router scores ALL its
    outputs and normalises over all the chosen, whoever holds them; the
    bias enters the choice only."""
    k = model["num_experts_per_tok"]
    first = model.get("first_routed_expert", 0)
    s = jax.nn.sigmoid(x @ _f32(w["router"]))
    _, choice = jax.lax.top_k(s + w["router_bias"], k)
    weight = jnp.take_along_axis(s, choice, axis=1)
    weight = weight / (weight.sum(1, keepdims=True) + 1e-20)
    weight = weight * float(model["routed_scaling_factor"])
    onehot = jax.nn.one_hot(choice, s.shape[1], dtype=jnp.float32)
    every = jnp.einsum("ske,sk->se", onehot, weight)
    return every[:, first:first + model["num_experts"]]


def _expert_block(x, gate, up, down, g):
    """A block of experts [e, ...] for a block of tokens, weighed by g
    [S, e]."""
    a = (jax.nn.silu(jnp.einsum("sh,ehf->esf", x, _f32(gate)))
         * jnp.einsum("sh,ehf->esf", x, _f32(up)))
    y = jnp.einsum("esf,efh->esh", a, _f32(down))
    return jnp.einsum("esh,se->sh", y, g)


def shared_expert(model, w, x):
    return _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])


def experts_layer(model, w, x, make=make_experts, block=_expert_block):
    """Every held expert for every token, gated, ``EXPERT_BLOCK`` experts
    and ``TOKEN_BLOCK`` tokens at a time; plus the shared ones."""
    g = gates(model, w, x)
    out = (shared_expert(model, w, x) if model["num_shared_experts"]
           else jnp.zeros_like(x))
    for e0 in range(0, g.shape[1], EXPERT_BLOCK):
        n = min(EXPERT_BLOCK, g.shape[1] - e0)
        mats = make(model, w, e0, n)
        out = out + by_token_blocks(
            lambda xb, gb: block(xb, *mats, gb), x, g[:, e0:e0 + n])
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
        make_e = jax.jit(lambda w, e0, n: make_experts(model, w, e0, n),
                         static_argnums=2)

        # A wait after every block: dispatch runs ahead of the device, and
        # the blocks in flight would hold their temporaries all at once.
        def waited(fn):
            return lambda *a: jax.block_until_ready(fn(*a))
        heads = waited(jax.jit(
            lambda w, x, c, k_r, h0, n: head_block(model, w, x, c, k_r, h0,
                                                   n), static_argnums=5))
        attend = waited(jax.jit(_attend_block, static_argnums=4))
        block = waited(jax.jit(_expert_block))
        dense = waited(jax.jit(lambda w, x: mlp(model, w, x)))
        norm = jax.jit(lambda x: _rms(x, eps))
        run = {"L": lambda w, x: latent_attention(
                   model, w, x, lambda m, *a: heads(*a), attend),
               "-": lambda w, x: by_token_blocks(lambda xb: dense(w, xb), x),
               "E": lambda w, x: experts_layer(
                   model, w, x, lambda m, *a: make_e(*a), block)}

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
