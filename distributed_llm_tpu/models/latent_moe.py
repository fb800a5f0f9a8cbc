"""The latent-attention, routed-expert, multi-stream decoder family.

``ModelConfig.kv_lora_rank > 0`` selects it.  A layer is

- **latent attention (MLA)**: queries through a low-rank bottleneck,
  keys and values up-projected from ONE cached row a position —
  ``kv_lora_rank`` normalised latent numbers and ``qk_rope_head_dim``
  rotary numbers shared by every head — so the paged pool keeps
  ``cfg.cache_row_width`` numbers a token a layer and no heads.  Two
  attention forms read that row.  A chunk of a prompt up-projects the
  gathered table window to keys and values by heads (at an 8192 window
  and the 29B widths 69 GFLOP a layer a chunk, against 146 for scores
  in the latent space).  A decode step folds the up-projection into the
  query and the output (the "absorbed" form): scores against the cached
  row itself, values read as the latent, K and V never materialised.
- a **dense SwiGLU** (the first ``dense_lead_layers``) or **dropless
  token-choice experts**: a float32 router scores every expert
  (sigmoid) from the float32 normed input, the top ``experts_per_token``
  of score + bias are chosen (the bias enters the choice only), weighed
  by their normalised scores times ``router_scale``; the assignments are
  sorted by expert and go through one grouped product
  (``jax.lax.ragged_dot``), so a step reads only the experts its tokens
  chose and nothing is ever dropped; ``shared_experts`` add for every
  token.
- **hyper-connections**: the residual is ``residual_streams`` copies of
  the hidden width; each sublayer reads a learned mix of them and writes
  back through a doubly-stochastic map (Sinkhorn, unrolled).

ONE layer body (``_block``), parameterised by the query length, serves
the chunk program and the decode tick (engine/paged_kv.py dispatches
here), and the cold prefill through a scratch pool of its own.  Nothing
in the body lowers to a loop: the benchmark tells a decode tick from a
prefill program by how deep its ``while``s nest, so the lead layers run
inline and the expert layers are the one ``scan``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..ops import grouped_product, latent_chunk_attention, quant
from . import transformer

Params = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST
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


# =============================================================================
# Init: the seed is data, never a constant of the program
# =============================================================================

def init_normal(key, shape, dtype, std=WEIGHT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


TABLE_ROWS = 4096       # most rows of a vocabulary table drawn at a time


def init_table(key, rows: int, width: int, dtype, std=WEIGHT_STD):
    """A [rows, width] vocabulary table (embedding, head), drawn a block
    of rows at a time from a key a block — the largest divisor of ``rows``
    that is at most ``TABLE_ROWS`` (4096 for a vocabulary that is a
    multiple of it, 3126 for 200 064): the float32 draws beside the result
    are one block's, not the table's 1.9 GB."""
    block = max(n for n in range(1, TABLE_ROWS + 1) if rows % n == 0)
    keys = jax.random.split(key, rows // block)
    return jax.lax.map(lambda k: init_normal(k, (block, width), dtype, std),
                       keys).reshape(rows, width)


def _hc_params(cfg: ModelConfig, key, dtype) -> Params:
    """One sublayer's stream maps: ``phi`` [n*H, n + n + n*n] (pre, post,
    res side by side), their three gains and the biases.  Gains of
    0.2..0.4 and unit-normal biases put ``H_res`` far from the identity
    and ``H_pre`` far from uniform, so a wrong mixing moves the logits."""
    n, h = cfg.residual_streams, cfg.hidden_size
    kp, ka, kb = jax.random.split(key, 3)
    return {"phi": init_normal(kp, (n * h, 2 * n + n * n), dtype),
            "alpha": jax.random.uniform(ka, (3,), jnp.float32, 0.2, 0.4),
            "b": jax.random.normal(kb, (2 * n + n * n,), jnp.float32)}


def init_layer(cfg: ModelConfig, key, moe: bool) -> Params:
    """One layer from its own key (``jax.random.split`` 16 ways; the
    experts' matrices split once more, a key an expert)."""
    dtype = jnp.dtype(cfg.dtype)
    h, nh = cfg.hidden_size, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 16)
    lp = {
        "ln1": jnp.ones((h,), dtype),
        "w_qa": init_normal(ks[0], (h, cfg.q_lora_rank), dtype),
        "q_ln": jnp.ones((cfg.q_lora_rank,), dtype),
        "w_qb": init_normal(ks[1], (cfg.q_lora_rank, nh * (dn + dr)), dtype),
        "w_kva": init_normal(ks[2], (h, cfg.cache_row_width), dtype),
        "kv_ln": jnp.ones((cfg.kv_lora_rank,), dtype),
        "w_kvb": init_normal(ks[3], (cfg.kv_lora_rank, nh * (dn + dv)), dtype),
        "wo": init_normal(ks[4], (nh * dv, h), dtype),
        "hc_attn": _hc_params(cfg, ks[5], dtype),
        "hc_ffn": _hc_params(cfg, ks[6], dtype),
        "ln2": jnp.ones((h,), dtype),
    }
    if not moe:
        f = cfg.ffn_size
        lp.update(w_gate=init_normal(ks[7], (h, f), dtype),
                  w_up=init_normal(ks[8], (h, f), dtype),
                  w_down=init_normal(ks[9], (f, h), dtype))
        return lp
    f, e = cfg.moe_ffn_size, cfg.num_experts
    fs = f * cfg.shared_experts

    def experts(key, shape):
        # One expert at a time: the float32 draws beside the result are
        # one expert's, not the layer's 0.9 GB a matrix.
        return jax.lax.map(lambda k: init_normal(k, shape, dtype),
                           jax.random.split(key, e))

    lp.update(router=init_normal(ks[10], (h, e), dtype),
              router_bias=ROUTER_BIAS_STD * jax.random.normal(
                  ks[11], (e,), jnp.float32),
              we_gate=experts(ks[12], (h, f)),
              we_up=experts(ks[13], (h, f)),
              we_down=experts(ks[14], (f, h)))
    if fs:
        lp.update(ws_gate=init_normal(ks[7], (h, fs), dtype),
                  ws_up=init_normal(ks[8], (h, fs), dtype),
                  ws_down=init_normal(ks[9], (fs, h), dtype))
    return lp


def init_params(cfg: ModelConfig, seed=0) -> Params:
    """``seed`` may be traced: jit this with the seed as an ARGUMENT and
    one compiled program makes every seed's weights.  (As a constant the
    compiler folds the random numbers at compile time, anew for every
    seed — ROADMAP S2.)  Layers are made one at a time (``lax.map``), so
    the temporaries beside the result are one layer's."""
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_head, k_layers = jax.random.split(jax.random.PRNGKey(seed), 3)
    lkeys = jax.random.split(k_layers, cfg.num_layers)
    n_lead = cfg.dense_lead_layers
    params = {
        "embed": init_table(k_embed, cfg.vocab_size, cfg.hidden_size, dtype,
                            EMBED_STD),
        "final_ln": jnp.ones((cfg.hidden_size,), dtype),
        "lead": jax.lax.map(lambda k: init_layer(cfg, k, False),
                            lkeys[:n_lead]),
        "layers": jax.lax.map(lambda k: init_layer(cfg, k, True),
                              lkeys[n_lead:]),
    }
    if not cfg.tie_embeddings:
        params["head"] = init_table(k_head, cfg.vocab_size, cfg.hidden_size,
                                    dtype)
    return params


# =============================================================================
# Rotary embedding with YaRN
# =============================================================================

def yarn_inv_freq(cfg: ModelConfig) -> jax.Array:
    """Inverse frequencies of the rotary half-pairs, [qk_rope_head_dim/2].
    YaRN as DeepSeek-V3 reads these keys: each frequency is a blend of the
    plain one and the one slowed by ``rope_factor``, by a linear ramp
    between the pairs that turn ``rope_beta_fast`` and ``rope_beta_slow``
    times over the original context."""
    dim = cfg.qk_rope_head_dim
    plain = cfg.rope_theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim)
    if cfg.rope_factor <= 1.0:
        return plain

    def turns_at(n_rot):
        return (dim * math.log(cfg.rope_original_max_pos
                               / (n_rot * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))
    low = max(math.floor(turns_at(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain / cfg.rope_factor * ramp + plain * (1.0 - ramp)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def rope_sincos(cfg: ModelConfig, positions: jax.Array):
    """(sin, cos) [..., qk_rope_head_dim/2] float32, times YaRN's
    magnitude ratio (1 where ``mscale == mscale_all_dim``)."""
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.sin(ang) * m, jnp.cos(ang) * m


def softmax_scale(cfg: ModelConfig) -> float:
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


# =============================================================================
# Hyper-connections
# =============================================================================

def stream_maps(cfg: ModelConfig, hc: Params, x: jax.Array):
    """x [T, n, H] -> (pre [n, T], post [n, T], res [n, n, T]) float32,
    the tokens on the last axis (the chip's lanes; the sums below then
    run over leading axes).  ``res[:, :, t]`` is doubly stochastic:
    Sinkhorn's alternating row and column normalisations of
    exp(clamp(.)), the ``hc_sinkhorn_iters`` rounds unrolled — a loop
    here would nest one more ``while`` into every step program."""
    n = cfg.residual_streams
    xf = x.reshape(x.shape[0], -1)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                            + cfg.norm_eps)
    m = jnp.einsum("td,dk->tk", xf, hc["phi"].astype(jnp.float32),
                   precision=HIGHEST)
    gain = hc["alpha"][np.repeat(np.arange(3), [n, n, n * n])]
    m = (m * gain + hc["b"]).T                              # [2n+n*n, T]
    pre = jax.nn.sigmoid(m[:n])
    post = 2.0 * jax.nn.sigmoid(m[n:2 * n])
    res = jnp.exp(jnp.clip(m[2 * n:], -cfg.hc_clamp, cfg.hc_clamp))
    res = res.reshape(n, n, -1)                             # [row, col, T]
    for _ in range(cfg.hc_sinkhorn_iters):
        res = res / (jnp.sum(res, axis=1, keepdims=True) + cfg.hc_eps)
        res = res / (jnp.sum(res, axis=0, keepdims=True) + cfg.hc_eps)
    return pre, post, res


def _hyper(cfg: ModelConfig, hc: Params, x: jax.Array, sublayer):
    """x <- H_res x + H_post^T F(H_pre x) over the streams of x
    [B, S, n, H] float32; ``sublayer`` maps the mixed [B, S, H] input
    (float32) to its output and whatever else it returns beside it.
    The streams stay float32 from embedding to final norm: they are
    the accumulator of every sublayer, 4 x hidden numbers a token, and
    rounded to bfloat16 after each sublayer their error alone flipped a
    top-k choice in one (token, layer) of 60 (PERF.md section 6, PR 29)."""
    n = cfg.residual_streams
    b, s, _, h = x.shape
    with jax.named_scope("hyper_connection"):
        pre, post, res = stream_maps(cfg, hc, x.reshape(b * s, n, h))
        xs = [x[..., j, :].reshape(b * s, h) for j in range(n)]
        mixed = sum(pre[j][:, None] * xs[j] for j in range(n))
    out, extra = sublayer(mixed.reshape(b, s, h))
    with jax.named_scope("hyper_connection"):
        y = out.astype(jnp.float32).reshape(b * s, h)
        new = [sum(res[i, j][:, None] * xs[j] for j in range(n))
               + post[i][:, None] * y for i in range(n)]
        x = jnp.stack(new, axis=1).reshape(b, s, n, h)
    return x, extra


# =============================================================================
# Latent attention over the paged pool
# =============================================================================

_einsum_f32 = latent_chunk_attention.einsum_f32


def _rope_1(x, sin, cos):
    """Rotate-half on a head-less [..., D] row."""
    return transformer.apply_rope(x[..., None, :], sin, cos)[..., 0, :]


def chunk_attention_form(cfg: ModelConfig, queries: int, window: int,
                         row: int, dtype) -> str:
    """What ``_attend`` traces a chunk's attention with, ``queries``
    tokens a sequence against ``window`` gathered rows ``row`` wide:
    ``blocks``, the kernel of ``ops/latent_chunk_attention.py`` (a block
    of the window up-projected beside its use, no scores in memory), or
    ``plain`` (``einsum`` + ``softmax`` over the whole up-projected
    window).  A test on static shapes and nothing else."""
    return "blocks" if latent_chunk_attention.serves(
        queries, window, cfg.num_heads, cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank, row,
        dtype) else "plain"


def _attend(cfg: ModelConfig, lp: Params, h_in, sin, cos, q_pos, pool_c, i,
            blk, off, tables, absorbed: bool):
    """h_in [B, S, H] -> (attention output [B, S, N*dv], pool array).

    Writes the chunk's cache rows at ``(i, blk, off)`` first, then
    attends the rows the ``tables`` [B, wb] name, position p of a
    sequence at flat index p of its gathered window; query (b, s) sees
    columns ``<= q_pos[b, s]``.  Queries come through the low-rank pair
    the layer holds, or — a layer that holds ONE query matrix ``wq`` (the
    hybrid family's "L", models/hybrid_ssm.py) — straight from it;
    ``sin`` None applies no rotary embedding: the ``qk_rope_head_dim``
    shared numbers are written and read as projected."""
    b, s, _ = h_in.shape
    nh, dc = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dtype = h_in.dtype

    if "w_qa" in lp:
        c_q = transformer.rms_norm(quant.matmul(h_in, lp["w_qa"]),
                                   lp["q_ln"], cfg.norm_eps)
        q = quant.matmul(c_q, lp["w_qb"])
    else:
        q = quant.matmul(h_in, lp["wq"])
    q = q.reshape(b, s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = quant.matmul(h_in, lp["w_kva"])
    k_rope = kv[..., dc:]
    if sin is not None:
        q_rope = transformer.apply_rope(q_rope, sin, cos)
        k_rope = _rope_1(k_rope, sin, cos)
    # The row as it rests: zeros behind its numbers up to the pool's whole
    # lane-widths (``cfg.cache_row_rest_width``), which nothing reads.
    rest = pool_c.shape[-1] - dc - dr
    row = jnp.concatenate(
        [transformer.rms_norm(kv[..., :dc], lp["kv_ln"], cfg.norm_eps),
         k_rope, jnp.zeros(k_rope.shape[:-1] + (rest,), k_rope.dtype)],
        axis=-1)                                             # [B, S, R]

    with jax.named_scope("kv_write"):
        pool_c = pool_c.at[i, blk, off].set(row)
    with jax.named_scope("latent_attention"):
        # One gather at (layer, block): no layer-sized slice in between.
        rows = pool_c[i, tables]                          # [B, wb, bs, R]
        rows = rows.reshape(b, -1, rows.shape[-1])        # [B, W, R]
        c, k_r = rows[..., :dc], rows[..., dc:dc + dr]
        w_kvb = quant.dequantize(lp["w_kvb"]).reshape(dc, nh, dn + dv)
        if not absorbed:
            # A chunk: the window's rows up-projected to keys and values,
            # a block at a time beside their use or the whole of it.
            attend = (latent_chunk_attention.latent_chunk_attention
                      if chunk_attention_form(cfg, s, rows.shape[1],
                                              rows.shape[2],
                                              rows.dtype) == "blocks"
                      else latent_chunk_attention.plain)
            out = attend(q_nope, q_rope, rows, w_kvb.astype(dtype), q_pos,
                         scale=softmax_scale(cfg))
            return out.reshape(b, s, nh * dv), pool_c
        # A step, absorbed: scores against the cached row itself, the
        # up-projection of the keys folded into the query ...
        q_lat = _einsum_f32("bsnd,cnd->bsnc", q_nope,
                            w_kvb[..., :dn]).astype(dtype)
        scores = (_einsum_f32("bsnc,bwc->bnsw", q_lat, c)
                  + _einsum_f32("bsnr,bwr->bnsw", q_rope, k_r))
        cols = jnp.arange(rows.shape[1])
        mask = cols[None, None, None, :] <= q_pos[:, None, :, None]
        scores = jnp.where(mask, scores * softmax_scale(cfg), -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(dtype)
        # ... and that of the values into the output.
        o_lat = _einsum_f32("bnsw,bwc->bsnc", p, c).astype(dtype)
        out = _einsum_f32("bsnc,cnd->bsnd", o_lat, w_kvb[..., dn:])
    return out.astype(dtype).reshape(b, s, nh * dv), pool_c


# =============================================================================
# Experts
# =============================================================================

def route(cfg: ModelConfig, lp: Params, x: jax.Array):
    """x [T, H] -> (choice [T, k] int32, weight [T, k] float32).  The
    router runs in float32 (the published implementation's precision),
    sigmoid scores.  The bias moves the CHOICE only."""
    logits = jnp.einsum("th,he->te", x.astype(jnp.float32),
                        lp["router"].astype(jnp.float32), precision=HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, choice = jax.lax.top_k(s + lp["router_bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(s, choice, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * cfg.router_scale
    return choice.astype(jnp.int32), w


def grouped_impl(rows: int, w) -> str:
    """Which grouped product ``_grouped`` traces for ``rows`` rows against
    ``w`` [G, in, out] (an array, its shape, or int8): ``pallas``, the
    repo's kernel for few rows a group (``ops/grouped_product.py``), or
    ``ragged_dot``, XLA's.  A test on static shapes and nothing else."""
    if quant.is_quantized(w):
        return "ragged_dot"
    groups, k, n = w.shape
    return ("pallas" if grouped_product.serves(rows, groups, k, n, w.dtype)
            else "ragged_dot")


def _grouped(x, w, sizes, group_of_row):
    """Rows of ``x`` sorted by group, times their group's matrix of
    ``w`` [G, in, out] (plain or int8): ONE product of the chain
    ``expert_ffn`` runs where the fused call does not serve.  Rows past
    the groups' (the hybrid family's absent-expert rows) are zeros from
    the kernel and whatever ``ragged_dot`` leaves there: the caller masks
    them."""
    if grouped_impl(x.shape[0], w) == "pallas":
        return grouped_product.grouped_product(x, w, sizes)
    if not quant.is_quantized(w):
        return jax.lax.ragged_dot(x, w, sizes)
    y = jax.lax.ragged_dot(x, w["q"].astype(x.dtype), sizes)
    return y * w["s"][:, 0][group_of_row]


def ffn_impl(rows: int, gate, up, down) -> str:
    """What ``expert_ffn`` traces for ``rows`` rows against a layer's
    experts (``gate`` None where they have none): ``pallas_ffn``, the
    whole FFN as ONE call of ``grouped_product.grouped_ffn``, or the chain
    of ``_grouped`` calls (``grouped_impl`` of each).  A test on static
    shapes and nothing else."""
    if not any(quant.is_quantized(w) for w in (up, down)):
        (groups, k, f), n = up.shape, down.shape[2]
        if grouped_product.serves_ffn(rows, groups, k, f, n, up.dtype,
                                      gate is not None):
            return "pallas_ffn"
    return "+".join(sorted({grouped_impl(rows, w) for w in (gate, up, down)
                            if w is not None}))


def expert_ffn(xs, gate, up, down, sizes, group_of_row):
    """Rows of ``xs`` sorted by group through their group's expert:
    ``silu(xs @ gate) * (xs @ up)`` (``relu(xs @ up)^2`` where ``gate`` is
    None), then ``@ down``; each product and the activation
    (``grouped_product.activation``: float32, rounded once) rounded to
    ``xs``'s dtype.  ONE kernel call where ``ffn_impl`` says so, which
    rounds at the same places: a call of its own a product leaves its
    first read hidden behind nothing and its last product with nothing in
    flight, and XLA's small fusion of the activation stands between two
    (6-10 us a call where a matrix streams in 10-16: PERF.md sections 5
    and 6, PR 53)."""
    if ffn_impl(xs.shape[0], gate, up, down) == "pallas_ffn":
        return grouped_product.grouped_ffn(xs, gate, up, down, sizes)
    u = _grouped(xs, up, sizes, group_of_row)
    h = (grouped_product.activation(u) if gate is None else
         grouped_product.activation(_grouped(xs, gate, sizes, group_of_row),
                                    u))
    return _grouped(h, down, sizes, group_of_row)


def grouped_product_form(cfg: ModelConfig, stacks, tokens: int) -> str:
    """What a program over ``tokens`` tokens traces its routed experts'
    FFN with (GET /stats ``moe.grouped_product``): ``pallas_ffn`` (one
    fused call a layer), ``pallas`` (a call a product) or ``ragged_dot``.
    ``stacks`` the experts' arrays as the tree holds them ((gate,) up,
    down), each [layers, experts, in, out] or int8 (then a layer at a
    time, XLA's)."""
    rows = tokens * cfg.experts_per_token
    *gate, up, down = (
        w if quant.is_quantized(w) else
        jax.ShapeDtypeStruct((w.shape[0] * w.shape[1], *w.shape[2:]),
                             w.dtype)
        for w in stacks)
    return ffn_impl(rows, gate[0] if gate else None, up, down)


EXPERT_KEYS = ("we_gate", "we_up", "we_down")


def expert_stacks(params: Params):
    """The routed experts' arrays as the tree holds them."""
    return [params["layers"][key] for key in EXPERT_KEYS]


def routed_experts(cfg: ModelConfig, lp: Params, x: jax.Array,
                   stacked: Optional[Params] = None, layer=None):
    """x [T, H] -> (the routed output [T, H] in the model's dtype,
    assignments an expert [num_experts] int32).  Dropless: every token's
    ``experts_per_token`` assignments are computed whatever the skew.
    The router reads ``x`` as given — inside the block the float32
    normed input, not its rounding to the model's dtype: a top-k choice
    made by a hair flips on less than bfloat16's 8 bits, and a flip moves
    that token's logits by a third (most flips come from the bfloat16
    sublayers before; this one costs nothing: PERF.md section 6, PR 29).
    The experts' products take ``x`` in the model's dtype.

    The experts' matrices come from ``lp`` ([experts, in, out], one
    layer's) or, inside the layer loop, from ``stacked`` — ALL the expert
    layers' matrices [layers, experts, in, out] as the tree holds them,
    with ``layer`` the traced index of this one.  The grouped product is
    then over layers x experts groups of which only this layer's are
    non-empty: a kernel cannot read through a per-layer slice of a
    scanned array, so slicing copied every expert of the layer every step
    (1.4 GB a layer: PERF.md section 6, PR 29); the groups no token chose
    are never read."""
    t, h = x.shape
    k, e = cfg.experts_per_token, cfg.num_experts
    xd = x.astype(jnp.dtype(cfg.dtype))
    with jax.named_scope("moe_router"):
        choice, w = route(cfg, lp, x)
        flat = choice.reshape(-1)                                 # [T*k]
        counts = jnp.sum(flat[:, None] == jnp.arange(e), axis=0,
                         dtype=jnp.int32)
        order = jnp.argsort(flat, stable=True)
        mats, sizes = lp, counts
        if stacked is not None:
            n = stacked[EXPERT_KEYS[0]].shape[0]
            mats = {key: stacked[key].reshape(n * e, *stacked[key].shape[2:])
                    for key in EXPERT_KEYS}
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros(n * e, jnp.int32), counts, (layer * e,))
    with jax.named_scope("moe_experts"):
        expert = flat[order]
        xs = xd[order // k]                                    # [T*k, H]
        y = expert_ffn(xs, mats["we_gate"], mats["we_up"], mats["we_down"],
                       sizes, expert)
        y = y[jnp.argsort(order)].reshape(t, k, h)
        out = jnp.einsum("tkh,tk->th", y.astype(jnp.float32), w)
    return out.astype(xd.dtype), counts


def _ffn(cfg: ModelConfig, lp: Params, h_in: jax.Array, moe: bool,
         stacked: Optional[Params] = None, layer=None):
    """h_in [B, S, H] float32 (normed) -> (FFN output in the model's
    dtype, expert counts or None)."""
    hd = h_in.astype(jnp.dtype(cfg.dtype))
    if not moe:
        return transformer._swiglu(hd, lp["w_gate"], lp["w_up"],
                                   lp["w_down"]), None
    b, s, h = h_in.shape
    out, counts = routed_experts(cfg, lp, h_in.reshape(b * s, h), stacked,
                                 layer)
    out = out.reshape(b, s, h)
    if "ws_gate" in lp:
        with jax.named_scope("shared_expert"):
            out = out + transformer._swiglu(hd, lp["ws_gate"], lp["ws_up"],
                                            lp["ws_down"])
    return out, counts


# =============================================================================
# The layer body and the forward pass over the paged pool
# =============================================================================

def _block(cfg: ModelConfig, lp: Params, x, pool_c, i, *, moe: bool,
           sin, cos, q_pos, blk, off, tables, absorbed: bool,
           stacked: Optional[Params] = None):
    """One layer over x [B, S, n, H] float32, pool layer ``i``; returns
    (x, pool array, counts).  ``stacked``: see ``routed_experts``."""
    def attention(mixed):
        with jax.named_scope("mixer_proj"):
            h_in = transformer.rms_norm(mixed, lp["ln1"], cfg.norm_eps)
            out, pool = _attend(cfg, lp, h_in.astype(jnp.dtype(cfg.dtype)),
                                sin, cos, q_pos, pool_c, i, blk, off, tables,
                                absorbed)
            return quant.matmul(out, lp["wo"]), pool

    def ffn(mixed):
        with jax.named_scope("ffn"):
            return _ffn(cfg, lp, transformer.rms_norm(mixed, lp["ln2"],
                                                      cfg.norm_eps), moe,
                        stacked, i - cfg.dense_lead_layers)

    x, pool_c = _hyper(cfg, lp["hc_attn"], x, attention)
    x, counts = _hyper(cfg, lp["hc_ffn"], x, ffn)
    return x, pool_c, counts


def forward_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  positions: jax.Array, q_pos: jax.Array, pool,
                  blk: jax.Array, off: jax.Array, tables: jax.Array,
                  absorbed: Optional[bool] = None):
    """tokens/positions/q_pos/blk/off [B, S]; tables [B, wb]; ``pool``
    {"c": [L, NB, bs, R]}.  Returns (hidden [B, S, H] after the final
    norm, pool, counts [expert layers, num_experts]).  ``absorbed``
    defaults to the query length: one token a sequence is a decode step."""
    if absorbed is None:
        absorbed = tokens.shape[1] == 1
    n = cfg.residual_streams
    x = quant.embed_rows(params["embed"], tokens)             # [B, S, H]
    with jax.named_scope("embed"):
        x = jnp.broadcast_to(x.astype(jnp.float32)[..., None, :],
                             x.shape[:-1] + (n, x.shape[-1]))
    with jax.named_scope("step_inputs"):
        sin, cos = rope_sincos(cfg, positions)
    kw = dict(sin=sin, cos=cos, q_pos=q_pos, blk=blk, off=off,
              tables=tables, absorbed=absorbed)
    pool_c = pool["c"]
    n_lead = cfg.dense_lead_layers
    # The lead layers run inline, so the expert layers' scan stays the
    # program's one layer loop.
    for i in range(n_lead):
        lp = jax.tree.map(lambda a: a[i], params["lead"])
        x, pool_c, _ = _block(cfg, lp, x, pool_c, i, moe=False, **kw)

    # The experts' matrices stay OUT of what the loop slices a layer
    # (``routed_experts``); int8 ones are widened a layer at a time.
    layers = dict(params["layers"])
    stacked = None
    if not quant.is_quantized(layers[EXPERT_KEYS[0]]):
        stacked = {key: layers.pop(key) for key in EXPERT_KEYS}

    def body(carry, scanned):
        x, pool_c = carry
        lp, i = scanned
        x, pool_c, counts = _block(cfg, lp, x, pool_c, i, moe=True,
                                   stacked=stacked, **kw)
        return (x, pool_c), counts

    with jax.named_scope("layer_scan"):
        (x, pool_c), counts = jax.lax.scan(
            body, (x, pool_c), (layers, jnp.arange(n_lead, cfg.num_layers)))
    with jax.named_scope("head"):
        x = jnp.sum(x, axis=-2).astype(jnp.dtype(cfg.dtype))
        hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return hidden, {"c": pool_c}, counts


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions: jax.Array):
    """Cold prefill of whole right-padded prompts [B, S]: the same body
    over a scratch pool of one block a sequence.  Returns (hidden,
    (rows [L, B, S, R],)) — the rows to page into the real pool."""
    b, s = tokens.shape
    scratch = {"c": jnp.zeros((cfg.num_layers, b, s,
                               cfg.cache_row_rest_width),
                              jnp.dtype(cfg.dtype))}
    seq = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s))
    hidden, scratch, _ = forward_paged(
        cfg, params, tokens, positions, positions, scratch, seq, positions,
        jnp.arange(b)[:, None], absorbed=False)
    return hidden, (scratch["c"],)
