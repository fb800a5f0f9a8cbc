"""The state-space / window-attention / shared-K/V decoder family
("decoder-hybrid-decoder": Phi-4-mini-flash-reasoning's block).

An ``F`` in ``ModelConfig.layer_pattern`` selects it.  EVERY layer is two
sublayers, ``x <- x + mixer(LN1(x))`` then ``x <- x + W2(silu(g) * u)``
with ``[g | u] = W1 LN2(x)``; ``LN`` is LayerNorm with gain and bias; the
head is the embedding, tied; no rotary or other positional term.  The
mixer by the layer's character:

- ``M``, **Mamba-1**: ``[a | z] = h W_in``; ``a <- silu(conv(a) + b)``
  (causal depthwise, ``ssm_conv`` taps); ``[delta | B | C] = a W_x``;
  ``dt = softplus(delta W_dt + b_dt)`` a CHANNEL; ``A = -exp(A_log)`` a
  channel AND state; ``S_t = exp(dt_t A) * S_{t-1} + (dt_t a_t) (x) B_t``;
  ``m_t = S_t C_t + D a_t``; out ``(m * silu(z)) W_out``.  The decay is
  not a scalar a head, so the chunk recurrence has no matrix form
  (models/hybrid_ssm.py's Mamba-2): a chunk's positions are stepped IN
  ORDER by a Pallas kernel (``ops/ssm_chunk_scan.py``: the loop is inside
  the kernel, nothing lowers to a ``while``), float32, the reference's
  own order of operations.  The mixer is ``hybrid_ssm.mamba1``, the one
  copy both row families run; this family's has no inner norms.  A sequence keeps ``S`` and the
  conv's last ``ssm_conv - 1`` input rows a layer, in a ROW of
  ``pool["s"]`` / ``pool["t"]``.  ``S`` rests as ``[state, inner]``: the
  channels fill the chip's 128 lanes (``[inner, 16]`` would rest padded
  eightfold).  The pattern's last ``M`` layer's ``m`` (before the gate,
  ``D`` term included) is the step's MEMORY.
- ``W``, **window attention**: ``[q | k | v] = h W_qkv + b``; position
  ``t`` attends ``t - attn_window + 1 .. t``.  Its K/V live in a RING of
  ``attn_window`` positions a slot (``pool["rk"]`` / ``pool["rv"]``, the
  row that holds the sequence's state), position ``p`` at ``p % ring``: a
  window layer's memory a slot does not grow with the sequence.  A chunk
  attends the ring as its earlier chunks left it plus its OWN rows from
  registers, then writes its valid rows (so the ring is exactly the
  window, and a chunk may not be longer than it).
- ``F``, **full attention**: as ``W`` without a window.  Its K/V are the
  ONLY K/V the paged pool holds (``pool["k"]`` / ``pool["v"]``, one
  layer).
- ``X``, **cross attention**: a query projection only, causal attention
  over layer ``F``'s K/V through the same block tables; no K/V of its own.
- ``G``, **gated memory unit**: out ``(m * silu(h W_1)) W_2``, ``m`` the
  memory of the SAME token; no state.
- **Differential heads** in all attention layers: query heads in pairs
  ``(2p, 2p+1)``, K/V heads in pairs, query pair ``p`` reads K/V pair ``p
  // (query heads / K/V heads)``; ``o_p = softmax(q_2p k_1) v - lam
  softmax(q_2p+1 k_2) v`` with ``v`` the pair's two value heads side by
  side, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init
  = 0.8 - 0.6 exp(-0.3 i)`` (``i`` the layer's index); an RMSNorm over the
  pair's width with a gain, times ``1 - lam_init``.

Whose row, and the engine's side of it, are models/hybrid_ssm.py's
(``claim_row``, ``rows_of``, ``chunk_ctx``): a ring is a row like a state.

The layer loop runs over ``cfg.layer_segments``: a ``scan`` a segment
that repeats, the layers of one that does not inline.  ONE body a kind
serves the chunk program and the decode tick.

The SELF-ONLY chunk: every layer after ``F`` reads, of other positions,
only ``F``'s K/V and writes no state, so its output at a prompt position
feeds nothing but that position's own logits.  A chunk that does not hold
the prompt's last token therefore runs the layers up to ``F``'s K/V write
and returns (``ctx["last"]``, a ``lax.cond`` around the rest): the pool it
leaves is the same arrays, bit for bit, and its hidden states — which no
caller reads — are zeros.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops import quant
from ..ops.attention import NEG_INF
from . import hybrid_ssm
# The ONE Mamba-1 mixer, both row families' (here without inner norms).
from .hybrid_ssm import (init_mamba1, mamba1 as _mamba,   # noqa: F401
                         mamba1_scan as ssm_scan, mamba1_step as ssm_step,
                         scan_unrolled)
from .latent_moe import EMBED_STD, init_normal, init_table

Params = Dict[str, Any]
KINDS = "MWFGX"
LAMBDA_STD = 0.1
POOL_KEYS = ("k", "v", "rk", "rv", "s", "t")


def check(cfg: ModelConfig) -> None:
    """The pattern and the sizes that have to agree with it."""
    p = cfg.layer_pattern
    bad = sorted(set(p) - set(KINDS))
    if bad or len(p) != cfg.num_layers:
        raise ValueError(
            f"{cfg.name}: layer_pattern {p!r} has to be num_layers = "
            f"{cfg.num_layers} characters of {KINDS!r}")
    f = p.index("F")
    if (p.count("F") != 1 or "M" not in p[:f] or set(p[f:]) & set("MW")
            or set(p[:f]) & set("GX")):
        raise ValueError(
            f"{cfg.name}: layer_pattern {p!r} has to be state-space and "
            f"window layers (M, W), then ONE F, then G and X layers")
    if (cfg.ssm_dt_rank <= 0 or cfg.ssm_head_dim != 1 or cfg.rotary
            or not cfg.tie_embeddings or cfg.attn_window <= 0):
        raise ValueError(
            f"{cfg.name}: the shared-K/V family is written for Mamba-1 "
            f"(ssm_dt_rank > 0, ssm_head_dim 1), a window, a tied head "
            f"and no rotary embedding")
    if cfg.num_heads % cfg.num_kv_heads or cfg.num_kv_heads % 2:
        raise ValueError(f"{cfg.name}: differential heads pair the K/V "
                         f"heads ({cfg.num_kv_heads}) and the query heads "
                         f"({cfg.num_heads}) that read each")


# =============================================================================
# Init: the seed is data, never a constant of the program
# =============================================================================

def init_layer(cfg: ModelConfig, key, kind: str) -> Params:
    """One layer from its own key, split 16 ways.  Every bias is drawn at
    the weights' scale (the conv's like its taps, the time step's from the
    published range): a dropped bias moves the logits."""
    dtype = jnp.dtype(cfg.dtype)
    h, f = cfg.hidden_size, cfg.ffn_size
    di, n, k, r = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    d = cfg.head_dim
    nq, nkv = cfg.num_heads * d, cfg.num_kv_heads * d
    ks = jax.random.split(key, 16)
    lp = {"ln1_w": jnp.ones((h,), dtype),
          "ln1_b": init_normal(ks[0], (h,), dtype),
          "ln2_w": jnp.ones((h,), dtype),
          "ln2_b": init_normal(ks[1], (h,), dtype),
          "w1": init_normal(ks[2], (h, 2 * f), dtype),
          "w2": init_normal(ks[3], (f, h), dtype)}
    if kind == "M":
        lp.update(init_mamba1(cfg, ks[4:11]))
    elif kind in "WFX":
        if kind == "X":
            lp.update(wq=init_normal(ks[4], (h, nq), dtype),
                      b_q=init_normal(ks[5], (nq,), dtype))
        else:
            lp.update(w_qkv=init_normal(ks[4], (h, nq + 2 * nkv), dtype),
                      b_qkv=init_normal(ks[5], (nq + 2 * nkv,), dtype))
        lp.update(wo=init_normal(ks[6], (nq, h), dtype),
                  b_o=init_normal(ks[7], (h,), dtype),
                  # lq1, lk1, lq2, lk2
                  lam=LAMBDA_STD * jax.random.normal(ks[8], (4, d),
                                                     jnp.float32),
                  sub_w=jnp.ones((2 * d,), dtype))
    else:
        lp.update(w_g1=init_normal(ks[4], (h, di), dtype),
                  w_g2=init_normal(ks[5], (di, h), dtype))
    return lp


def init_params(cfg: ModelConfig, seed=0) -> Params:
    """``seed`` may be traced.  ``segments[s][j]`` holds position ``j`` of
    segment ``s``'s period for every repeat, stacked; layer ``l`` draws
    from key ``l`` of ``num_layers``."""
    check(cfg)
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_final, k_layers = jax.random.split(jax.random.PRNGKey(seed), 3)
    lkeys = jax.random.split(k_layers, cfg.num_layers)
    segments, base = [], 0
    for period, reps in cfg.layer_segments:
        n = len(period)
        segments.append([
            jax.lax.map(lambda k, c=kind: init_layer(cfg, k, c),
                        lkeys[base + j:base + n * reps:n])
            for j, kind in enumerate(period)])
        base += n * reps
    return {"embed": init_table(k_embed, cfg.vocab_size, cfg.hidden_size,
                                dtype, EMBED_STD),
            "final_ln_w": jnp.ones((cfg.hidden_size,), dtype),
            "final_ln_b": init_normal(k_final, (cfg.hidden_size,), dtype),
            "segments": segments}


# =============================================================================
# The mixers
# =============================================================================

def layer_norm(x, w, b, eps):
    """LayerNorm with gain and bias, computed in float32, in ``x``'s
    dtype out."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _pairing(cfg: ModelConfig):
    """(K/V pairs, query pairs a K/V pair)."""
    return cfg.num_kv_heads // 2, cfg.num_heads // cfg.num_kv_heads


def diff_split(cfg: ModelConfig, q, k, v, mask):
    """Both softmaxes of every pair with the head axis split off: q [T,
    query heads, D], k and v [S, K/V heads * D] as they rest, mask [T, S].
    Returns [T, query heads, 2 D]: query head ``h``'s probabilities over
    its pair's two value heads side by side.  A chunk's form."""
    t, nq, d = q.shape
    g, c = _pairing(cfg)
    s = k.shape[0]
    q = q.reshape(t, g, c, 2, d)                # h = 2 c g + 2 c' + e
    k = k.reshape(s, g, 2, d)
    v = v.reshape(s, g, 2 * d)
    scores = jnp.einsum("tgced,sged->gcets", q, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("gcets,sgf->tgcef", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(t, nq, 2 * d)


def diff_merged(cfg: ModelConfig, q, k_rows, v_rows, mask):
    """The same for ONE query a sequence, over rows whose heads stay
    merged on the minor axis (``ops.attention.merged_decode_attention``'s
    form, for the same reason: no head axis is split off a window-sized
    array): q [B, query heads, D], k_rows and v_rows [B, S, K/V heads *
    D], mask [B, S].  Returns [B, query heads, 2 D] float32."""
    b, nq, d = q.shape
    g, c = _pairing(cfg)
    heads = jnp.arange(nq)
    # Query head h reads key head 2 * (h // (2 c)) + h % 2 ...
    own_k = ((2 * (heads // (2 * c)) + heads % 2)[:, None]
             == jnp.arange(2 * g)[None, :])[None, :, :, None]
    q_rows = jnp.where(own_k, q[:, :, None, :], 0).reshape(b, nq, 2 * g * d)
    logits = jnp.einsum("bnc,bkc->bnk", q_rows, k_rows,
                        preferred_element_type=jnp.float32) * d ** -0.5
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_rows.dtype)
    full = jnp.einsum("bnk,bkc->bnc", probs, v_rows,
                      preferred_element_type=jnp.float32)
    # ... and BOTH value heads of K/V pair h // (2 c).
    own_v = ((heads // (2 * c))[:, None]
             == jnp.arange(g)[None, :])[None, :, :, None]
    return jnp.where(own_v, full.reshape(b, nq, g, 2 * d), 0).sum(axis=2)


def lambda_init(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_combine(cfg: ModelConfig, lp: Params, o, layer, dtype):
    """o [..., query heads, 2 D] float32 -> [..., query heads * D] in
    ``dtype``: the pair's difference, its RMSNorm and gain, times ``1 -
    lam_init``."""
    with jax.named_scope("diff_combine"):
        lam_i = lambda_init(layer)
        lq1, lk1, lq2, lk2 = lp["lam"]
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + lam_i)
        o = o.reshape(*o.shape[:-2], cfg.num_heads // 2, 2, o.shape[-1])
        o = o[..., 0, :] - lam * o[..., 1, :]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.norm_eps)
        o = o * lp["sub_w"].astype(jnp.float32) * (1.0 - lam_i)
        return o.reshape(*o.shape[:-2], -1).astype(dtype)


def _qkv(cfg: ModelConfig, lp: Params, h_in):
    nq = cfg.num_heads * cfg.head_dim
    nkv = cfg.cache_row_width
    qkv = quant.matmul(h_in, lp["w_qkv"]) + lp["b_qkv"]
    return qkv[..., :nq], qkv[..., nq:nq + nkv], qkv[..., nq + nkv:]


def _out_proj(cfg, lp, o, layer, dtype):
    return quant.matmul(diff_combine(cfg, lp, o, layer, dtype),
                        lp["wo"]) + lp["b_o"]


def _window(cfg: ModelConfig, lp: Params, h_in, pool, wi, layer, ctx):
    """h_in [B, S, H] -> (mixer output, pool); ``wi`` the layer's index
    among the window layers, which are the rings' layers."""
    b, s, _ = h_in.shape
    d, w = cfg.head_dim, cfg.attn_window
    q, k, v = _qkv(cfg, lp, h_in)
    q = q.reshape(b, s, cfg.num_heads, d)
    rk, rv = pool["rk"], pool["rv"]
    ring = rk.shape[2]
    slots = jnp.arange(ring)
    with jax.named_scope("window_attention"):
        if "row" in ctx:
            row, start = ctx["row"], ctx["start"][0]
            # Slot j holds the last position before the chunk that is j
            # modulo the ring; a position below 0 was never written by
            # this sequence.
            held = start - 1 - (start - 1 - slots) % ring
            own = start + jnp.arange(s)
            key_pos = jnp.concatenate([held, own])
            mask = ((key_pos[None, :] <= own[:, None])
                    & (key_pos[None, :] > own[:, None] - w)
                    & (key_pos[None, :] >= 0))
            o = diff_split(cfg, q[0],
                           jnp.concatenate([rk[wi, row], k[0]]),
                           jnp.concatenate([rv[wi, row], v[0]]), mask)[None]
            at = jnp.where(jnp.arange(s) < ctx["n_valid"], own % ring, ring)
            rk = rk.at[wi, row, at].set(k[0], mode="drop")
            rv = rv.at[wi, row, at].set(v[0], mode="drop")
        else:
            src, valid, dst = ctx["rows"]
            pos = ctx["pos"][src]                                  # [R]
            at = jnp.where(valid, pos % ring, ring)
            rows = jnp.arange(rk.shape[1])
            rk = rk.at[wi, rows, at].set(k[src, 0], mode="drop")
            rv = rv.at[wi, rows, at].set(v[src, 0], mode="drop")
            held = pos[:, None] - (pos[:, None] - slots[None, :]) % ring
            mask = (held >= 0) & (held > pos[:, None] - w)
            o = diff_merged(cfg, q[src, 0], rk[wi], rv[wi], mask)
            o = o[dst][:, None]
    return (_out_proj(cfg, lp, o, layer, h_in.dtype),
            {**pool, "rk": rk, "rv": rv})


def _shared_write(cfg: ModelConfig, lp: Params, h_in, pool, ctx):
    """Layer ``F``'s projections, its K/V rows written to the paged pool
    (its one layer).  Returns (q, pool)."""
    q, k, v = _qkv(cfg, lp, h_in)
    with jax.named_scope("shared_kv_write"):
        blk, off = ctx["blk"], ctx["off"]
        pool = {**pool, "k": pool["k"].at[0, blk, off].set(k),
                "v": pool["v"].at[0, blk, off].set(v)}
    return q, pool


def _shared_attention(cfg: ModelConfig, lp: Params, q, pool, layer, ctx,
                      dtype):
    """q [B, S, query heads * D] over layer ``F``'s K/V by the block
    tables: ``F`` itself and every ``X`` layer."""
    b, s, _ = q.shape
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k_p, v_p = pool["k"][0], pool["v"][0]                  # [NB, bs, row]
    bs = k_p.shape[1]
    with jax.named_scope("shared_kv_attention"):
        if "row" in ctx:
            blocks = ctx["table"][:ctx["window"] // bs]
            k = k_p[blocks].reshape(-1, k_p.shape[-1])
            v = v_p[blocks].reshape(-1, v_p.shape[-1])
            mask = jnp.arange(k.shape[0])[None, :] <= ctx["q_pos"][0][:, None]
            o = diff_split(cfg, q[0], k, v, mask)[None]
        else:
            tables = ctx["tables"]
            k = k_p[tables].reshape(b, -1, k_p.shape[-1])
            v = v_p[tables].reshape(b, -1, v_p.shape[-1])
            mask = jnp.arange(k.shape[1])[None, :] <= ctx["pos"][:, None]
            o = diff_merged(cfg, q[:, 0], k, v, mask)[:, None]
    return _out_proj(cfg, lp, o, layer, dtype)


def _cross(cfg, lp, h_in, pool, layer, ctx):
    q = quant.matmul(h_in, lp["wq"]) + lp["b_q"]
    return _shared_attention(cfg, lp, q, pool, layer, ctx, h_in.dtype)


def _gated_memory(lp: Params, h_in, mem):
    with jax.named_scope("gated_memory"):
        gate = jax.nn.silu(quant.matmul(h_in, lp["w_g1"]).astype(jnp.float32))
        return quant.matmul((mem * gate).astype(h_in.dtype), lp["w_g2"])


def _mlp(cfg: ModelConfig, lp: Params, x):
    with jax.named_scope("ffn"):
        gu = quant.matmul(layer_norm(x, lp["ln2_w"], lp["ln2_b"],
                                     cfg.norm_eps), lp["w1"])
        f = cfg.ffn_size
        return x + quant.matmul(jax.nn.silu(gu[..., :f]) * gu[..., f:],
                                lp["w2"])


# =============================================================================
# The forward pass over the paged pool
# =============================================================================

def _layer(cfg, kind, lp, x, pool, mem, layer, index, ctx):
    """One layer of ``kind`` but ``F``: (x, pool, mem).  ``layer`` its
    index in the model, ``index`` among the layers of its kind."""
    # Whatever of a mixer no scope of its own names: the norm, the
    # projections, the residual.
    with jax.named_scope("mixer_proj"):
        h_in = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        if kind == "M":
            out, pool, mem = _mamba(cfg, lp, h_in, pool, index, ctx)
        elif kind == "W":
            out, pool = _window(cfg, lp, h_in, pool, index, layer, ctx)
        elif kind == "G":
            out = _gated_memory(lp, h_in, mem)
        else:
            out = _cross(cfg, lp, h_in, pool, layer, ctx)
        x = x + out
    return _mlp(cfg, lp, x), pool, mem


def _run_segments(cfg, segments, x, pool, mem, ctx):
    """Segments ``segments`` [(base layer, period, repeats, weights)] of
    the pattern, none holding ``F``."""
    pattern = cfg.layer_pattern
    for base, period, reps, weights in segments:
        before = [pattern[:base + j].count(kind)
                  for j, kind in enumerate(period)]

        def body(carry, scanned, base=base, period=period, before=before):
            x, pool, mem = carry
            lps, r = scanned
            for j, kind in enumerate(period):
                x, pool, mem = _layer(
                    cfg, kind, lps[j], x, pool, mem,
                    base + r * len(period) + j,
                    before[j] + r * period.count(kind), ctx)
            return (x, pool, mem), None

        if reps == 1:
            (x, pool, mem), _ = body(
                (x, pool, mem),
                ([jax.tree_util.tree_map(lambda a: a[0], lp)
                  for lp in weights], 0))
        else:
            with jax.named_scope("layer_scan"):
                (x, pool, mem), _ = jax.lax.scan(
                    body, (x, pool, mem), (weights, jnp.arange(reps)))
    return x, pool, mem


def forward_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  pool, ctx: Dict[str, Any]):
    """tokens [B, S]; ``pool`` {"k", "v": [1, NB, bs, K/V heads * D],
    "rk", "rv": [window layers, R, ring, K/V heads * D], "s": [state-space
    layers, R, state, inner] float32, "t": [state-space layers, R, K-1,
    inner], "owner": [R]}.  ``ctx`` is ``chunk_ctx`` / ``decode_ctx``.
    Returns (hidden [B, S, H] after the final norm, pool); a chunk that
    is not the prompt's last (``ctx["last"]`` false) returns zeros for
    hidden and runs no layer after ``F``'s K/V write."""
    dtype = jnp.dtype(cfg.dtype)
    x = quant.embed_rows(params["embed"], tokens).astype(dtype)
    owner = pool["owner"]
    carried = {key: pool[key] for key in POOL_KEYS}
    mem = jnp.zeros(tokens.shape + (cfg.ssm_inner,), jnp.float32)

    segments, base = [], 0
    for (period, reps), weights in zip(cfg.layer_segments,
                                       params["segments"]):
        segments.append((base, period, reps, weights))
        base += len(period) * reps
    f_at = next(i for i, seg in enumerate(segments) if seg[1] == "F")
    f_layer = segments[f_at][0]
    lp_f = jax.tree_util.tree_map(lambda a: a[0], segments[f_at][3][0])

    x, carried, mem = _run_segments(cfg, segments[:f_at], x, carried,
                                    mem, ctx)
    with jax.named_scope("mixer_proj"):
        h_in = layer_norm(x, lp_f["ln1_w"], lp_f["ln1_b"], cfg.norm_eps)
        q, carried = _shared_write(cfg, lp_f, h_in, carried, ctx)

    def rest(x):
        with jax.named_scope("mixer_proj"):
            x = x + _shared_attention(cfg, lp_f, q, carried, f_layer, ctx,
                                      dtype)
        x = _mlp(cfg, lp_f, x)
        x, _, _ = _run_segments(cfg, segments[f_at + 1:], x, carried,
                                mem, ctx)
        with jax.named_scope("head"):
            return layer_norm(x, params["final_ln_w"], params["final_ln_b"],
                              cfg.norm_eps)

    if "last" in ctx:
        hidden = jax.lax.cond(ctx["last"], rest, jnp.zeros_like, x)
    else:
        hidden = rest(x)
    return hidden, {**carried, "owner": owner}


def chunk_ctx(pool, table, start, true_len, s_c: int, window: int,
              blk, off, q_pos):
    """``hybrid_ssm.chunk_ctx`` and whether the chunk holds the prompt's
    last token."""
    ctx, pool = hybrid_ssm.chunk_ctx(pool, table, start, true_len, s_c,
                                     window, blk, off, q_pos)
    return {**ctx, "last": start[0] + s_c >= true_len[0]}, pool


decode_ctx = hybrid_ssm.decode_ctx
