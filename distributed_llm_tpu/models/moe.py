"""Mixture-of-Experts transformer with expert parallelism (the 'ep' axis).

New capability (the reference has no intra-model parallelism at all,
SURVEY.md §2.2); this is the TPU-idiomatic MoE recipe: dense einsum
dispatch/combine with a static capacity (GShard/Switch style) so shapes
stay fixed under jit, experts stacked on a leading [E] axis that GSPMD
shards over the mesh's 'ep' axis — the all-to-alls fall out of the einsum
shardings, no hand-written collectives.

Layer structure mirrors models/transformer.py (RMSNorm / RoPE / GQA
attention / scanned layers); only the FFN is replaced by top-2 routed
experts.  Prefill additionally returns the load-balancing auxiliary loss
(Switch §2.2: E · Σ_e fraction_e · mean_prob_e), which the trainer adds to
the LM loss.  Decode computes every expert for the (few) decode tokens and
combines by gate weight — at batch-size-per-step scale that is cheaper and
simpler than capacity dispatch.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops import attention
from ..ops import quant
from . import transformer

Params = Dict[str, Any]


# =============================================================================
# Init
# =============================================================================

def init_params(cfg: ModelConfig, seed: int = 0) -> Params:
    """Dense-transformer params with the FFN replaced by E stacked experts
    plus a router; structure otherwise matches transformer.init_params."""
    base = transformer.init_params(cfg, seed)
    key = jax.random.PRNGKey(seed ^ 0x3E0E)
    dtype = jnp.dtype(cfg.dtype)
    h, f, l, e = cfg.hidden_size, cfg.ffn_size, cfg.num_layers, cfg.num_experts

    def normal(key, shape, scale=0.02):
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    ks = jax.random.split(key, 4)
    layers = dict(base["layers"])
    for dense_key in ("w_gate", "w_up", "w_down"):
        layers.pop(dense_key)
    layers.update({
        "w_router": normal(ks[0], (l, h, e)),
        "w_gate": normal(ks[1], (l, e, h, f)),
        "w_up": normal(ks[2], (l, e, h, f)),
        "w_down": normal(ks[3], (l, e, f, h)),
    })
    return {**base, "layers": layers}


# =============================================================================
# Routed FFN
# =============================================================================

def _top2_gates(router_logits: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[T, E] logits -> (combine weights [T, E] with ≤2 nonzeros renormed,
    probs [T, E] float32 for the aux loss)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    e = probs.shape[-1]
    idx1 = jnp.argmax(probs, axis=-1)
    mask1 = jax.nn.one_hot(idx1, e, dtype=probs.dtype)
    masked = probs * (1.0 - mask1)
    idx2 = jnp.argmax(masked, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=probs.dtype)
    gates = probs * (mask1 + mask2)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, probs


def moe_ffn_train(cfg: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Capacity-dispatch MoE FFN for full sequences.

    x: [B, S, H] -> (out [B, S, H], aux loss scalar).  Tokens over
    capacity for their expert are dropped (contribute zero), the standard
    static-shape trade-off.
    """
    b, s, h = x.shape
    t = b * s
    e = cfg.num_experts
    xt = x.reshape(t, h)

    gates, probs = _top2_gates(quant.matmul(xt, lp["w_router"]))          # [T, E]

    capacity = max(1, int(cfg.moe_capacity_factor * 2 * t / e))
    # Position of each token within its expert's buffer, per expert.
    sel = (gates > 0).astype(jnp.int32)                      # [T, E]
    pos = jnp.cumsum(sel, axis=0) * sel - 1                  # [T, E]
    keep = (pos >= 0) & (pos < capacity)
    pos = jnp.clip(pos, 0, capacity - 1)

    # dispatch [T, E, C]: one-hot of each kept token's buffer slot.
    dispatch = (keep[..., None]
                & (jax.nn.one_hot(pos, capacity, dtype=jnp.bool_)))
    dispatch = dispatch.astype(x.dtype)
    combine = dispatch * gates.astype(x.dtype)[..., None]    # weights in

    expert_in = jnp.einsum("tec,th->ech", dispatch, xt)      # [E, C, H]
    gate_h = quant.expert_einsum("ech,ehf->ecf", expert_in, lp["w_gate"])
    up_h = quant.expert_einsum("ech,ehf->ecf", expert_in, lp["w_up"])
    act = jax.nn.silu(gate_h) * up_h
    expert_out = quant.expert_einsum("ecf,efh->ech", act, lp["w_down"])
    out = jnp.einsum("tec,ech->th", combine, expert_out)

    # Switch load-balance loss: E · Σ_e fraction_of_tokens_e · mean_prob_e.
    frac = jnp.mean((gates > 0).astype(jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return out.reshape(b, s, h), aux


def moe_ffn_decode(cfg: ModelConfig, lp: Dict[str, jax.Array], x: jax.Array
                   ) -> jax.Array:
    """Decode-step MoE FFN: x [B, H].  Computes all experts for the few
    decode tokens and combines by (top-2) gate weight — no dispatch."""
    gates, _ = _top2_gates(quant.matmul(x, lp["w_router"]))               # [B, E]
    gate_h = quant.expert_einsum("bh,ehf->bef", x, lp["w_gate"])
    up_h = quant.expert_einsum("bh,ehf->bef", x, lp["w_up"])
    act = jax.nn.silu(gate_h) * up_h
    outs = quant.expert_einsum("bef,efh->beh", act, lp["w_down"])     # [B, E, H]
    return jnp.einsum("be,beh->bh", gates.astype(x.dtype), outs)


# =============================================================================
# Forward passes (mirror transformer.prefill / decode_step)
# =============================================================================

def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions: jax.Array
            ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], jax.Array]:
    """Like transformer.prefill but returns (hidden, (k_all, v_all), aux):
    the summed load-balance loss across layers."""
    b, s = tokens.shape
    d = cfg.head_dim
    x = quant.embed_rows(params["embed"], tokens)
    sin, cos = transformer.rope_sincos(positions, d, cfg.rope_theta)

    def layer(carry, lp):
        x, aux = carry
        h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = transformer.project_qkv(cfg, lp, h_in)
        q = transformer.apply_rope(q, sin, cos)
        k = transformer.apply_rope(k, sin, cos)
        attn = attention.causal(q, k, v, impl=cfg.attention_impl
                                ).reshape(b, s, cfg.num_heads * d)
        x = x + quant.matmul(attn, lp["wo"])
        ffn_out, layer_aux = moe_ffn_train(
            cfg, lp, transformer.rms_norm(x, lp["ln2"], cfg.norm_eps))
        return (x + ffn_out, aux + layer_aux), (k, v)

    (x, aux), (k_all, v_all) = jax.lax.scan(
        layer, (x, jnp.float32(0.0)), params["layers"])
    hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return hidden, (k_all, v_all), aux


def chunk_prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  start: jax.Array, true_len: jax.Array,
                  kv: transformer.KVCache, window: int = 0
                  ) -> Tuple[jax.Array, transformer.KVCache]:
    """Prefill a chunk against an existing cache — MoE twin of
    ``transformer.chunk_prefill`` (same contract; the chunk's tokens go
    through capacity-dispatch MoE FFN, aux loss dropped as in serving).
    Enables session KV prefix reuse (engine/prefix_cache.py) for MoE tiers.

    APPROXIMATE vs a cold full-history prefill: expert capacity is computed
    from the chunk's token count, so which tokens get capacity-dropped can
    differ from running the whole prompt at once — outputs are close
    (cosine ≈ 1) but not bit-identical.  Tiers needing exact replay should
    set enable_prefix_cache=False (see config.TierConfig).
    """
    b, s_c = tokens.shape
    d = cfg.head_dim
    x = quant.embed_rows(params["embed"], tokens)
    positions = start[:, None] + jnp.arange(s_c)[None, :]
    q_pos = jnp.minimum(positions, jnp.maximum(true_len, 1)[:, None] - 1)
    sin, cos = transformer.rope_sincos(positions, d, cfg.rope_theta)

    def layer(x, scanned):
        lp, k_cache, v_cache = scanned
        h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = transformer.project_qkv(cfg, lp, h_in)
        q = transformer.apply_rope(q, sin, cos)
        k = transformer.apply_rope(k, sin, cos)

        def write(cache, new):
            def one(c, n, p):
                return jax.lax.dynamic_update_slice(c, n, (p, 0, 0))
            return jax.vmap(one)(cache, new, start)
        k_cache = write(k_cache, k)
        v_cache = write(v_cache, v)

        k_att = k_cache[:, :window] if window else k_cache
        v_att = v_cache[:, :window] if window else v_cache
        attn = attention.chunk(q, k_att, v_att, q_pos)
        x = x + quant.matmul(attn.reshape(b, s_c, cfg.num_heads * d), lp["wo"])
        ffn_out, _ = moe_ffn_train(
            cfg, lp, transformer.rms_norm(x, lp["ln2"], cfg.norm_eps))
        return x + ffn_out, (k_cache, v_cache)

    x, (k_new, v_new) = jax.lax.scan(
        layer, x, (params["layers"], kv["k"], kv["v"]))
    hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return hidden, {"k": k_new, "v": v_new}


def decode_step(cfg: ModelConfig, params: Params, token: jax.Array,
                pos: jax.Array, kv: transformer.KVCache
                ) -> Tuple[jax.Array, transformer.KVCache]:
    """One autoregressive step; same contract as transformer.decode_step."""
    b = token.shape[0]
    d = cfg.head_dim
    x = quant.embed_rows(params["embed"], token)
    sin, cos = transformer.rope_sincos(pos, d, cfg.rope_theta)

    def layer(x, scanned):
        lp, k_cache, v_cache = scanned
        h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = transformer.project_qkv(cfg, lp, h_in)
        q = transformer.apply_rope(q, sin, cos)
        k = transformer.apply_rope(k, sin, cos)

        def write(cache, new):
            def one(c, n, p):
                return jax.lax.dynamic_update_slice(c, n[None], (p, 0, 0))
            return jax.vmap(one)(cache, new, pos)
        k_cache = write(k_cache, k)
        v_cache = write(v_cache, v)

        attn = attention.decode(q, k_cache, v_cache, pos)
        x = x + quant.matmul(attn.reshape(b, cfg.num_heads * d), lp["wo"])
        x = x + moe_ffn_decode(
            cfg, lp, transformer.rms_norm(x, lp["ln2"], cfg.norm_eps))
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = jax.lax.scan(
        layer, x, (params["layers"], kv["k"], kv["v"]))
    hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return transformer.logits_from_hidden(params, hidden), \
        {"k": k_new, "v": v_new}
