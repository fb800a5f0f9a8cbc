"""Pure-JAX LLaMA-style decoder-only transformer.

This is the native model-execution core that the reference delegates to
Ollama/llama.cpp (SURVEY.md §2.1): RMSNorm, rotary position embeddings,
grouped-query attention, SwiGLU MLP, tied LM head.  Design choices are
TPU-first:

- **Scanned layers**: per-layer parameters are stacked along a leading [L]
  axis and the forward pass is a single ``lax.scan`` over layers, so compile
  time is O(1) in depth and XLA sees one fused block body.
- **Functional params pytree** (no framework Module): makes pjit/shard_map
  sharding annotations trivial (parallel/sharding.py maps each leaf to a
  PartitionSpec) and keeps everything donate-able.
- **bfloat16 params/activations** with float32 softmax/norm accumulators —
  the MXU-native layout.
- Static shapes everywhere; the decode step is one token per call and is
  driven by a compiled ``lax.while_loop`` (engine/inference.py).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops import attention
from ..ops import quant

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]   # {"k": [L,B,S,N_kv,D], "v": [L,B,S,N_kv,D]}


# =============================================================================
# Init
# =============================================================================

def init_params(cfg: ModelConfig, seed: int = 0) -> Params:
    """Deterministic random init (no pretrained weights exist in this
    zero-egress environment; quality of text is not the contract, the
    execution engine is)."""
    key = jax.random.PRNGKey(seed)
    dtype = jnp.dtype(cfg.dtype)
    h, f, l = cfg.hidden_size, cfg.ffn_size, cfg.num_layers
    d = cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads

    def normal(key, shape, scale=0.02):
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    ks = jax.random.split(key, 8)
    return {
        "embed": normal(ks[0], (cfg.vocab_size, h)),
        "layers": {
            "ln1": jnp.ones((l, h), dtype),
            "wq": normal(ks[1], (l, h, nq * d)),
            "wk": normal(ks[2], (l, h, nkv * d)),
            "wv": normal(ks[3], (l, h, nkv * d)),
            "wo": normal(ks[4], (l, nq * d, h)),
            "ln2": jnp.ones((l, h), dtype),
            "w_gate": normal(ks[5], (l, h, f)),
            "w_up": normal(ks[6], (l, h, f)),
            "w_down": normal(ks[7], (l, f, h)),
        },
        "final_ln": jnp.ones((h,), dtype),
    }


# =============================================================================
# Building blocks
# =============================================================================

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def rope_sincos(positions: jax.Array, head_dim: int, theta: float
                ) -> Tuple[jax.Array, jax.Array]:
    """positions [...,] -> (sin, cos) each [..., head_dim/2], float32."""
    freqs = theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32)
                      / (head_dim // 2))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Rotate-half RoPE. x: [..., N, D]; sin/cos: [..., D/2] (broadcast over N)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    sin, cos = sin[..., None, :], cos[..., None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _swiglu(x: jax.Array, gate, up, down) -> jax.Array:
    return quant.matmul(
        jax.nn.silu(quant.matmul(x, gate)) * quant.matmul(x, up), down)


def project_qkv(cfg: ModelConfig, lp: Params, h_in: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A layer's q, k and v projections with the heads split off:
    h_in [..., H] -> q [..., N_q, D], k and v [..., N_kv, D], before the
    rotary embedding.  The one spelling of it for every dense body
    (prefill, decode_step, chunk_prefill here; the three paged steps of
    engine/paged_kv.py).

    q and k cross an ``optimization_barrier`` as the dense [..., N·D]
    rows the product writes, and only then split heads (ISSUE 48).
    Without it the TPU compiler's layout assignment carries the
    ``[.., N, D]`` result's preferred layout back through the product
    into the WEIGHT: every tick then copies the whole ``wq`` and ``wk``
    stacks into that layout at its entry, and every layer of a tick or
    a chunk program writes its ``[H, N·D]`` matrix out before the
    product reads it, where ``wv``'s product reads the stack in place.
    The pin holds rows of activations (tiny beside a matrix), it is the
    identity on values (an int8 weight's scale has multiplied inside
    ``quant.matmul``, before it) and on the CPU; no weight changes shape
    or order at rest."""
    d = cfg.head_dim
    q, k = jax.lax.optimization_barrier(
        (quant.matmul(h_in, lp["wq"]), quant.matmul(h_in, lp["wk"])))
    v = quant.matmul(h_in, lp["wv"])
    lead = h_in.shape[:-1]
    return (q.reshape(*lead, cfg.num_heads, d),
            k.reshape(*lead, cfg.num_kv_heads, d),
            v.reshape(*lead, cfg.num_kv_heads, d))


# =============================================================================
# Prefill (full-sequence forward)
# =============================================================================

def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions: jax.Array, attn=None
            ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Process a full (right-padded) prompt.

    tokens/positions: [B, S].  Returns (hidden [B,S,H],
    (k_all, v_all) each [L,B,S,N_kv,D]) — the per-layer K/V to seed the cache.
    ``attn`` optionally replaces the causal-attention op (q, k, v) ->
    [B,S,Nq,D] — the hook sequence-parallel prefill uses to swap in ring
    attention over the 'sp' mesh axis (parallel/ring_attention.py).
    """
    b, s = tokens.shape
    d = cfg.head_dim
    x = quant.embed_rows(params["embed"], tokens)                       # [B,S,H]
    sin, cos = rope_sincos(positions, d, cfg.rope_theta)
    if attn is None:
        attn = lambda q, k, v: attention.causal(q, k, v,
                                                impl=cfg.attention_impl)

    def layer(x, lp):
        h_in = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = project_qkv(cfg, lp, h_in)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        out = attn(q, k, v).reshape(b, s, cfg.num_heads * d)
        x = x + quant.matmul(out, lp["wo"])
        x = x + _swiglu(rms_norm(x, lp["ln2"], cfg.norm_eps),
                        lp["w_gate"], lp["w_up"], lp["w_down"])
        return x, (k, v)

    x, (k_all, v_all) = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_ln"], cfg.norm_eps), (k_all, v_all)


def logits_from_hidden(params: Params, hidden: jax.Array) -> jax.Array:
    """LM head: [..., H] -> [..., V] in float32.  The tree's own "head"
    [V, H] where it holds one (``ModelConfig.tie_embeddings`` False),
    else the embedding, tied."""
    with jax.named_scope("head"):
        return quant.tied_head(params.get("head", params["embed"]), hidden)


# =============================================================================
# Decode step (one token, KV cache)
# =============================================================================

def decode_step(cfg: ModelConfig, params: Params, token: jax.Array,
                pos: jax.Array, kv: KVCache, attn=None
                ) -> Tuple[jax.Array, KVCache]:
    """One autoregressive step for every sequence in the batch.

    token: [B] current input token; pos: [B] its position (0-based);
    kv: cache with [L,B,S_max,N_kv,D] arrays, written in-place at ``pos``.
    ``attn`` optionally replaces the decode-attention op
    (q, k_cache, v_cache, pos) -> [B,Nq,D] — the hook sequence-parallel
    tiers use for their partial+merge decode (parallel/sp_attention.py).
    Returns (logits [B,V] float32, updated cache).
    """
    b = token.shape[0]
    d = cfg.head_dim
    x = quant.embed_rows(params["embed"], token)      # [B,H]
    sin, cos = rope_sincos(pos, d, cfg.rope_theta)    # [B, D/2]
    quantized = "ks" in kv
    if attn is None or quantized:
        # int8 caches always use the scale-aware op (the sp hook carries
        # no scale operands; engine/inference.py gives quantized tiers
        # none).
        attn = lambda q, kc, vc, p, ks=None, vs=None: attention.decode(
            q, kc, vc, p, k_scale=ks, v_scale=vs)
    else:
        base = attn
        attn = lambda q, kc, vc, p, ks=None, vs=None: base(q, kc, vc, p)

    def write_rows(cache, new):
        # Write this step's K/V (or scale) rows at each sequence's pos.
        def one(c, n, p):
            return jax.lax.dynamic_update_slice(
                c, n[None], (p,) + (0,) * (c.ndim - 1))
        return jax.vmap(one)(cache, new, pos)

    def layer(x, scanned):
        if quantized:
            lp, k_cache, v_cache, ks_cache, vs_cache = scanned
        else:
            lp, k_cache, v_cache = scanned
            ks_cache = vs_cache = None
        h_in = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = project_qkv(cfg, lp, h_in)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

        if quantized:
            k, k_sc = quant.quantize_kv_rows(k)
            v, v_sc = quant.quantize_kv_rows(v)
            ks_cache = write_rows(ks_cache, k_sc)
            vs_cache = write_rows(vs_cache, v_sc)
        k_cache = write_rows(k_cache, k)
        v_cache = write_rows(v_cache, v)

        attn_out = attn(q, k_cache, v_cache, pos, ks_cache, vs_cache)
        x = x + quant.matmul(attn_out.reshape(b, cfg.num_heads * d),
                             lp["wo"])
        x = x + _swiglu(rms_norm(x, lp["ln2"], cfg.norm_eps),
                        lp["w_gate"], lp["w_up"], lp["w_down"])
        if quantized:
            return x, (k_cache, v_cache, ks_cache, vs_cache)
        return x, (k_cache, v_cache)

    if quantized:
        x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
            layer, x, (params["layers"], kv["k"], kv["v"],
                       kv["ks"], kv["vs"]))
        new_kv = {"k": k_new, "v": v_new, "ks": ks_new, "vs": vs_new}
    else:
        x, (k_new, v_new) = jax.lax.scan(
            layer, x, (params["layers"], kv["k"], kv["v"]))
        new_kv = {"k": k_new, "v": v_new}
    hidden = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return logits_from_hidden(params, hidden), new_kv


def chunk_prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  start: jax.Array, true_len: jax.Array, kv: KVCache,
                  window: int = 0) -> Tuple[jax.Array, KVCache]:
    """Prefill a CHUNK of a prompt against an existing KV cache.

    The op behind session prefix reuse (engine/prefix_cache.py): when a new
    prompt extends a previously-served one (the multi-turn chat pattern —
    the reference re-prefills the whole history through Ollama every turn,
    SURVEY.md §3.1), only the suffix is forwarded here, attending to the
    cached prefix at absolute positions.  Also serves as plain chunked
    prefill (start=0 over successive chunks).

    tokens: [B, S_c] right-padded chunk; start: [B] absolute position of the
    chunk's first token (prefix length already in ``kv``); true_len: [B]
    total valid length (start + real chunk tokens); kv: [L,B,S_max,N_kv,D]
    cache, written in place at [start, start+S_c).
    ``window`` (static): attend only to cache positions < window instead of
    all S_max — callers pass a bucketed bound ≥ start+S_c so attention cost
    is O(prefix bucket), not O(max_seq).  0 = full cache.
    Returns (hidden [B,S_c,H], updated cache).
    """
    b, s_c = tokens.shape
    d = cfg.head_dim
    x = quant.embed_rows(params["embed"], tokens)                                    # [B,S_c,H]
    positions = start[:, None] + jnp.arange(s_c)[None, :]          # [B,S_c]
    # Queries past each sequence's true length are padding; clamp their mask
    # frontier to the last real position (their outputs are never read).
    q_pos = jnp.minimum(positions, jnp.maximum(true_len, 1)[:, None] - 1)
    sin, cos = rope_sincos(positions, d, cfg.rope_theta)

    quantized = "ks" in kv

    def write_rows(cache, new):
        def one(c, n, p):
            return jax.lax.dynamic_update_slice(
                c, n, (p,) + (0,) * (c.ndim - 1))
        return jax.vmap(one)(cache, new, start)

    def layer(x, scanned):
        if quantized:
            lp, k_cache, v_cache, ks_cache, vs_cache = scanned
        else:
            lp, k_cache, v_cache = scanned
            ks_cache = vs_cache = None
        h_in = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = project_qkv(cfg, lp, h_in)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

        if quantized:
            k, k_sc = quant.quantize_kv_rows(k)
            v, v_sc = quant.quantize_kv_rows(v)
            ks_cache = write_rows(ks_cache, k_sc)
            vs_cache = write_rows(vs_cache, v_sc)
        k_cache = write_rows(k_cache, k)
        v_cache = write_rows(v_cache, v)

        k_att = k_cache[:, :window] if window else k_cache
        v_att = v_cache[:, :window] if window else v_cache
        scales = ((ks_cache[:, :window] if window else ks_cache,
                   vs_cache[:, :window] if window else vs_cache)
                  if quantized else (None, None))
        attn = attention.chunk(q, k_att, v_att, q_pos,
                               k_scale=scales[0], v_scale=scales[1])
        x = x + quant.matmul(attn.reshape(b, s_c, cfg.num_heads * d), lp["wo"])
        x = x + _swiglu(rms_norm(x, lp["ln2"], cfg.norm_eps),
                        lp["w_gate"], lp["w_up"], lp["w_down"])
        if quantized:
            return x, (k_cache, v_cache, ks_cache, vs_cache)
        return x, (k_cache, v_cache)

    if quantized:
        x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
            layer, x, (params["layers"], kv["k"], kv["v"],
                       kv["ks"], kv["vs"]))
        new_kv = {"k": k_new, "v": v_new, "ks": ks_new, "vs": vs_new}
    else:
        x, (k_new, v_new) = jax.lax.scan(
            layer, x, (params["layers"], kv["k"], kv["v"]))
        new_kv = {"k": k_new, "v": v_new}
    hidden = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return hidden, new_kv


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int,
                  kv_quantize: str = "none") -> KVCache:
    """``kv_quantize="int8"``: K/V stored as symmetric per-row int8 with
    f32 scale planes {"ks","vs": [L,B,S,N_kv]} — decode streams the whole
    cache every step, so halving its bytes is a direct bandwidth win
    (ops/quant.quantize_kv_rows; the paged pool's contiguous twin)."""
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    if kv_quantize == "int8":
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.ones(shape[:-1], jnp.float32),
                "vs": jnp.ones(shape[:-1], jnp.float32)}
    if kv_quantize != "none":
        raise ValueError(f"kv_quantize={kv_quantize!r}: expected 'none' "
                         "or 'int8'")
    dtype = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def seed_kv_cache(cfg: ModelConfig, k_all: jax.Array, v_all: jax.Array,
                  cache_len: int, kv_quantize: str = "none") -> KVCache:
    """Build a cache of length ``cache_len`` holding a prefill's K/V
    ([L,B,S,N_kv,D]) at positions [0, S) — quantizing on write when the
    cache is int8."""
    b = k_all.shape[1]
    cache = init_kv_cache(cfg, b, cache_len, kv_quantize)
    if "ks" in cache:
        kq, ks = quant.quantize_kv_rows(k_all)
        vq, vs = quant.quantize_kv_rows(v_all)
        return {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq,
                                              (0, 0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq,
                                              (0, 0, 0, 0, 0)),
            "ks": jax.lax.dynamic_update_slice(cache["ks"], ks,
                                               (0, 0, 0, 0)),
            "vs": jax.lax.dynamic_update_slice(cache["vs"], vs,
                                               (0, 0, 0, 0)),
        }
    return {
        "k": jax.lax.dynamic_update_slice(cache["k"], k_all,
                                          (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v_all,
                                          (0, 0, 0, 0, 0)),
    }
