"""Plain reference: the latent-attention, routed-expert, multi-stream
decoder (Xing4.0-29B-A4B's block) in float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
the whole sequence goes through every layer; attention is the naive
form (keys and values up-projected by heads for every position, full
causal softmax; nothing absorbed, no cache); EVERY expert is computed
for every token and weighed by a gate that is zero for the experts the
token did not choose; no kernels, no batching tricks.  It takes nothing
from the program or the harness.

Per layer, with X [S, n, H] the n residual streams of a sequence:

    attention sublayer, then FFN sublayer, each as
      xt   = vec(X) / rms(vec(X))                       (over all n*H numbers)
      m    = gain * (xt @ phi) + b                      (n + n + n*n numbers)
      Hpre = sigmoid(m[:n]);  Hpost = 2 sigmoid(m[n:2n])
      Hres = Sinkhorn(exp(clamp(m[2n:].reshape(n, n), -c, c)))   (20 rounds:
             rows / (row sums + eps), then columns / (column sums + eps))
      X   <- Hres X + Hpost^T F(RMSNorm(Hpre X))

    attention F:  c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads of (nope, rope)
                  [c_kv ; k_r] = x W_kva;  c_kv <- RMSNorm(c_kv)
                  [k_nope ; v] = c_kv W_kvb by heads;  rotary on q_rope, k_r
                  softmax((q_nope k_nope + q_rope k_r) * scale, causal) v, W_o
    FFN F, dense layers:   SwiGLU(intermediate_size)
    FFN F, expert layers:  s = sigmoid(x W_r) in float32; top-k of s + bias;
                           weights s of the chosen / their sum * scale;
                           sum over the chosen experts + the shared expert

Departures from the published description, each because ``config.json``
is silent (the configuration file lists them under ``assumed``):
  * rotary pairs are (i, i + d/2) ("rotate half"), the program's
    convention, not interleaved;
  * the stream norm ``xt`` has no gain;
  * ``hc_eps`` enters the Sinkhorn denominators;
  * the streams start as n copies of the embedding and end summed;
  * the multi-token-prediction block is not run (it adds nothing to the
    next-token logits).

Weights come from the seed by the recipe of the program's
``models/latent_moe.py``, written out again here: ``PRNGKey(seed)`` split
3 ways (embedding, head, layers), the layers' key split a layer, a
layer's key split 16 ways, an expert matrix's key split an expert, a
vocabulary table's key split a block of 4096 rows;
normal(0, 0.02) rounded to the model's dtype (the embedding normal(0, 1)),
gains 1, stream-map gains
uniform(0.2, 0.4), stream-map biases normal(0, 1), router bias
normal(0, 0.01).  A layer's weights are made when the layer is run and
dropped after it, and its experts are widened to float32 a block at a
time, so that beside the engine's 9.6 GB the reference never holds more
than one layer.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

WEIGHT_STD = 0.02
# Small beside the scores' own spread (0.25 at the published widths): a
# bias of 0.1 sent 6 of a step's 8 tokens to the same experts (15 of 64
# read a layer where distinct tokens of a balanced router read 25.8).
ROUTER_BIAS_STD = 0.01
# The embedding at unit scale, as a trained model's hidden states are.  At
# 0.02 the attention's summary of a prompt (norm 7 where prompts are made of
# the same few symbols) outweighed the token's own row (norm 1.2), so every
# row of a step looked alike to the routers.
EMBED_STD = 1.0
EXPERT_BLOCK = 4        # experts widened to float32 and computed at a time
HEAD_BLOCK = 4          # heads whose [S, S] scores are held at a time


# -- weights from the seed ---------------------------------------------------

def _dtype(model):
    return jnp.dtype(model.get("torch_dtype", "bfloat16"))


def _normal(key, shape, dtype, std=WEIGHT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


TABLE_ROWS = 4096       # rows of a vocabulary table drawn at a time


def _table(key, rows, width, dtype, std=WEIGHT_STD):
    """A [rows, width] vocabulary table, a key a block of TABLE_ROWS."""
    block = min(rows, TABLE_ROWS)
    keys = jax.random.split(key, rows // block)
    return jax.lax.map(lambda k: _normal(k, (block, width), dtype, std),
                       keys).reshape(rows, width)


def _stream_maps(model, key, dtype):
    n, h = model["hc_mult"], model["hidden_size"]
    kp, ka, kb = jax.random.split(key, 3)
    return {"phi": _normal(kp, (n * h, 2 * n + n * n), dtype),
            "alpha": jax.random.uniform(ka, (3,), jnp.float32, 0.2, 0.4),
            "b": jax.random.normal(kb, (2 * n + n * n,), jnp.float32)}


def make_layer(model, key, moe: bool) -> Dict[str, Any]:
    dtype = _dtype(model)
    h, nh = model["hidden_size"], model["num_attention_heads"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, dc, dq = model["v_head_dim"], model["kv_lora_rank"], \
        model["q_lora_rank"]
    ks = jax.random.split(key, 16)
    w = {"w_qa": _normal(ks[0], (h, dq), dtype),
         "w_qb": _normal(ks[1], (dq, nh * (dn + dr)), dtype),
         "w_kva": _normal(ks[2], (h, dc + dr), dtype),
         "w_kvb": _normal(ks[3], (dc, nh * (dn + dv)), dtype),
         "wo": _normal(ks[4], (nh * dv, h), dtype),
         "hc_attn": _stream_maps(model, ks[5], dtype),
         "hc_ffn": _stream_maps(model, ks[6], dtype)}
    if not moe:
        f = model["intermediate_size"]
        w.update(w_gate=_normal(ks[7], (h, f), dtype),
                 w_up=_normal(ks[8], (h, f), dtype),
                 w_down=_normal(ks[9], (f, h), dtype))
        return w
    f, e = model["moe_intermediate_size"], model["n_routed_experts"]
    fs = f * model["n_shared_experts"]

    def experts(key, shape):
        # One expert at a time: the float32 draws beside the result are
        # one expert's, not the layer's 0.9 GB a matrix.
        return jax.lax.map(lambda k: _normal(k, shape, dtype),
                           jax.random.split(key, e))

    w.update(router=_normal(ks[10], (h, e), dtype),
             router_bias=ROUTER_BIAS_STD * jax.random.normal(
                  ks[11], (e,), jnp.float32),
             we_gate=experts(ks[12], (h, f)), we_up=experts(ks[13], (h, f)),
             we_down=experts(ks[14], (f, h)))
    if fs:
        w.update(ws_gate=_normal(ks[7], (h, fs), dtype),
                 ws_up=_normal(ks[8], (h, fs), dtype),
                 ws_down=_normal(ks[9], (fs, h), dtype))
    return w


def init_weights(model: Dict[str, Any], seed: int, sharding=None
                 ) -> Dict[str, Any]:
    """Keys only: the embedding's, the head's and one a layer.  Every
    array is made from its key when ``logits`` reaches it and dropped
    after, so the reference never holds more than one layer (or one
    vocabulary table) beside the engine.  The seed is an argument of the
    compiled maker."""
    def make(seed):
        k_embed, k_head, k_layers = jax.random.split(
            jax.random.PRNGKey(seed), 3)
        return {"k_embed": k_embed, "k_head": k_head,
                "layer_keys": jax.random.split(
                    k_layers, model["num_hidden_layers"])}
    out = jax.jit(make, out_shardings=sharding)(jnp.int32(seed))
    out["sharding"] = sharding
    return out


# -- the forward pass -----------------------------------------------------------

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(model):
    """YaRN as DeepSeek-V3 reads the keys: a blend, by a linear ramp over
    the rotary pairs, of the plain inverse frequency and the one divided
    by ``factor``."""
    dim, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    rs = model.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    if factor <= 1.0:
        return plain
    orig = rs["original_max_position_embeddings"]

    def pair_at(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair_at(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_at(rs.get("beta_slow", 1))), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def softmax_scale(model):
    rs = model.get("rope_scaling") or {}
    m = _mscale(float(rs.get("factor", 1.0)),
                float(rs.get("mscale_all_dim", 0.0)))
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(model, x):
    """x [S, N, D] at positions 0..S-1, pairs (i, i + D/2)."""
    rs = model.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    mag = (_mscale(factor, float(rs.get("mscale", 1.0)))
           / _mscale(factor, float(rs.get("mscale_all_dim", 0.0))))
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(model)
    sin, cos = jnp.sin(ang)[:, None] * mag, jnp.cos(ang)[:, None] * mag
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinkhorn(model, logits):
    """[..., n, n] -> doubly stochastic [..., n, n]."""
    lo, hi = model["mhc_h_res_clamp_min"], model["mhc_h_res_clamp_max"]
    m = jnp.exp(jnp.clip(logits, lo, hi))
    for _ in range(model["hc_sinkhorn_iters"]):
        m = m / (m.sum(-1, keepdims=True) + model["hc_eps"])
        m = m / (m.sum(-2, keepdims=True) + model["hc_eps"])
    return m


def _hyper_maps(model, hc, x):
    """x [S, n, H] -> (Hpre x [S, H], Hres [S, n, n], Hpost [S, n])."""
    n = model["hc_mult"]
    eps = float(model["rms_norm_eps"])
    xt = _rms(x.reshape(x.shape[0], -1), eps)
    m = xt @ hc["phi"].astype(jnp.float32)
    gain = jnp.concatenate([jnp.full((k,), hc["alpha"][i])
                            for i, k in enumerate((n, n, n * n))])
    m = m * gain + hc["b"]
    h_pre = jax.nn.sigmoid(m[:, :n])                           # [S, n]
    h_post = 2.0 * jax.nn.sigmoid(m[:, n:2 * n])               # [S, n]
    h_res = sinkhorn(model, m[:, 2 * n:].reshape(-1, n, n))    # [S, n, n]
    return jnp.einsum("sn,snh->sh", h_pre, x), h_res, h_post


def _hyper_write(x, h_res, h_post, y):
    return (jnp.einsum("sij,sjh->sih", h_res, x)
            + h_post[:, :, None] * y[:, None, :])


def _hyper(model, hc, x, f):
    """x [S, n, H] <- Hres x + Hpost^T f(Hpre x)."""
    u, h_res, h_post = _hyper_maps(model, hc, x)
    return _hyper_write(x, h_res, h_post, f(u))


def _attend_block(q_nope, q_rope, k_nope, k_r, v, scale):
    """Causal softmax attention of a block of heads: q_nope/k_nope
    [S, n, dn], q_rope [S, n, dr], k_r [S, dr] (one rotary key for all
    heads), v [S, n, dv] -> [S, n, dv]."""
    s = q_nope.shape[0]
    scores = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
              + jnp.einsum("qnr,kr->nqk", q_rope, k_r))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores * scale, -jnp.inf)
    return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)


def _attention(model, w, x, block=_attend_block):
    """x [S, H] (normalised) -> [S, H]; HEAD_BLOCK heads' scores at a
    time (``block`` may be the jitted ``_attend_block``: then one block's
    [S, S] scores live at a time)."""
    s = x.shape[0]
    nh = model["num_attention_heads"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, dc = model["v_head_dim"], model["kv_lora_rank"]
    eps = float(model["rms_norm_eps"])
    f32 = lambda a: a.astype(jnp.float32)
    q = (_rms(x @ f32(w["w_qa"]), eps) @ f32(w["w_qb"])).reshape(
        s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(model, q[..., dn:])
    kv = x @ f32(w["w_kva"])
    c_kv = _rms(kv[:, :dc], eps)
    k_r = _rope(model, kv[:, None, dc:])[:, 0]                  # [S, dr]
    kvb = (c_kv @ f32(w["w_kvb"])).reshape(s, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    outs = []
    for h0 in range(0, nh, HEAD_BLOCK):
        hb = slice(h0, h0 + HEAD_BLOCK)
        outs.append(block(q_nope[:, hb], q_rope[:, hb], k_nope[:, hb], k_r,
                          v[:, hb], softmax_scale(model)))
    return jnp.concatenate(outs, axis=1).reshape(s, nh * dv) @ f32(w["wo"])


def _swiglu(x, gate, up, down):
    f32 = lambda a: a.astype(jnp.float32)
    return (jax.nn.silu(x @ f32(gate)) * (x @ f32(up))) @ f32(down)


def gates(model, w, x):
    """x [S, H] -> [S, E] float32: the chosen experts' weights, zero for
    the rest.  The bias enters the choice only."""
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["router"].astype(jnp.float32))
    _, choice = jax.lax.top_k(s + w["router_bias"], k)
    chosen = jnp.take_along_axis(s, choice, axis=1)
    weight = chosen / (chosen.sum(1, keepdims=True) + 1e-20) \
        * float(model["routed_scaling_factor"])
    onehot = jax.nn.one_hot(choice, s.shape[1], dtype=jnp.float32)
    return jnp.einsum("ske,sk->se", onehot, weight)


def _expert_block(x, gate, up, down, g):
    """A block of experts [e, ...] for every token, weighed by g [S, e]."""
    f32 = lambda a: a.astype(jnp.float32)
    a = jnp.einsum("sh,ehf->esf", x, f32(gate))
    u = jnp.einsum("sh,ehf->esf", x, f32(up))
    y = jnp.einsum("esf,efh->esh", jax.nn.silu(a) * u, f32(down))
    return jnp.einsum("esh,se->sh", y, g)


def _experts(model, w, x, block=_expert_block):
    """Every expert for every token, gated, EXPERT_BLOCK at a time; plus
    the shared one.  ``block`` may be the jitted ``_expert_block``: then
    one block's float32 copies live at a time."""
    g = gates(model, w, x)
    out = jnp.zeros_like(x)
    for e0 in range(0, g.shape[1], EXPERT_BLOCK):
        eb = slice(e0, e0 + EXPERT_BLOCK)
        out = out + block(x, w["we_gate"][eb], w["we_up"][eb],
                          w["we_down"][eb], g[:, eb])
    if "ws_gate" in w:
        out = out + _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out


def dense_sublayer(model, w, x):
    eps = float(model["rms_norm_eps"])
    return _hyper(model, w["hc_ffn"], x, lambda u: _swiglu(
        _rms(u, eps), w["w_gate"], w["w_up"], w["w_down"]))


VOCAB_BLOCK = 16384     # rows of the head widened to float32 at a time


def logits(model: Dict[str, Any], weights: Dict[str, Any], tokens,
           keep) -> jax.Array:
    """Float32 logits [B, K, V] of a full forward pass over ``tokens``
    [B, S] (right-padded; padding never reaches an earlier position) at
    the positions ``keep`` [B, K].  One sequence at a time through one
    layer at a time; an expert layer's experts a block at a time."""
    n = model["hc_mult"]
    n_lead = model["first_k_dense_replace"]
    eps = float(model["rms_norm_eps"])
    sharding = weights.get("sharding")
    dtype = _dtype(model)
    shape = (model["vocab_size"], model["hidden_size"])
    with jax.default_matmul_precision("highest"):
        table = jax.jit(lambda k, std: _table(k, *shape, dtype, std),
                        out_shardings=sharding, static_argnums=1)
        make = {moe: jax.jit(lambda k, moe=moe: make_layer(model, k, moe),
                             out_shardings=sharding)
                for moe in (False, True)}
        # Dispatch runs ahead of the device, and a program's buffers are
        # taken when it is dispatched: without a wait after every block,
        # the blocks in flight hold their temporaries all at once (1.7 GB
        # beside the engine, my chip runs, PR 29).
        def waited(fn):
            return lambda *a: jax.block_until_ready(fn(*a))
        attend = waited(jax.jit(_attend_block, static_argnums=5))
        dense = jax.jit(lambda w, x: dense_sublayer(model, w, x))
        maps = jax.jit(lambda hc, x: _hyper_maps(model, hc, x))
        write = jax.jit(_hyper_write)
        block = waited(jax.jit(_expert_block))
        norm = jax.jit(lambda u: _rms(u, eps))

        embed = table(weights["k_embed"], EMBED_STD)
        xs = [jax.jit(lambda e, t: jnp.broadcast_to(
            e[t].astype(jnp.float32)[:, None, :],
            (t.shape[0], n, e.shape[1])))(embed, tokens[b])
            for b in range(tokens.shape[0])]
        if not model.get("tie_word_embeddings"):
            del embed
        for i in range(model["num_hidden_layers"]):
            w = make[i >= n_lead](weights["layer_keys"][i])
            for b in range(len(xs)):
                u, h_res, h_post = maps(w["hc_attn"], xs[b])
                x = write(xs[b], h_res, h_post,
                          _attention(model, w, norm(u), attend))
                if i < n_lead:
                    xs[b] = dense(w, x)
                    continue
                u, h_res, h_post = maps(w["hc_ffn"], x)
                xs[b] = write(x, h_res, h_post,
                              _experts(model, w, norm(u), block))
            del w, x

        head = (embed if model.get("tie_word_embeddings")
                else table(weights["k_head"], WEIGHT_STD))
        final = jax.jit(lambda x, kept: _rms(jnp.sum(x, axis=1)[kept], eps))
        kept = jnp.stack([final(x, keep[b]) for b, x in enumerate(xs)])
        rows = jax.jit(lambda a, h: a @ h.astype(jnp.float32).T)
        return jnp.concatenate(
            [rows(kept, head[v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, shape[0], VOCAB_BLOCK)], axis=-1)
