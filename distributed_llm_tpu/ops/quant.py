"""Weight-only int8 quantization for the serving path.

Autoregressive decode is HBM-bandwidth-bound: every step streams the full
weight set through the MXU for one token.  Storing weights as int8 with a
per-output-channel scale halves that traffic versus bfloat16 (the reference
leans on Ollama's GGML quantized formats for exactly this reason —
SURVEY.md §2.1); XLA fuses the dequantize cast into the matmul read, so the
compute stays MXU-shaped.

Representation: a quantized tensor is the dict ``{"q": int8, "s": scale}``
with ``w ≈ q * s`` broadcast over the contraction dimension — ``s`` has the
weight's trailing (output) dimension and the model dtype, so dequantization
is one cast + multiply.  Per-layer stacked weights [L, in, out] carry
``s: [L, 1, out]`` and slice cleanly through ``lax.scan``.

Serving-only: the trainer always sees full-precision params.  Sharded
(tp>1) tiers quantize too — the quantized pytree has its own
PartitionSpec map (parallel/sharding.py quantized_param_shardings: q
sharded like the weight, scales unsharded on their size-1 contraction
axis), so a tensor-parallel tier streams half the weight bytes per chip.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

QTensor = Dict[str, jax.Array]   # {"q": int8, "s": model-dtype scale}


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def quantize_kv_rows(x: jax.Array) -> tuple:
    """Symmetric per-row int8 for KV caches: scale over the trailing D
    axis.  Returns (int8 values, float32 scales with the D axis dropped).
    Shared by the paged pool (engine/paged_kv.py) and the contiguous
    cache (models/transformer.py) under ``TierConfig.kv_quantize``."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize_kv_rows(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantize_tensor(w: jax.Array, contract_axis: int = -2) -> QTensor:
    """Per-output-channel symmetric int8: scale over the contraction axis.

    ``contract_axis`` is the axis summed over in ``x @ w`` (default -2, the
    'in' dim of an [in, out] or [L, in, out] weight); each output channel
    gets max|w|/127.
    """
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=contract_axis, keepdims=True)
    # Round the scale to its storage dtype FIRST, then quantize with the
    # rounded value: for bf16 params the stored scale has 8 mantissa bits,
    # and quantizing against the unrounded f32 scale would bake a
    # per-channel multiplicative error into every reconstructed weight.
    scale = (jnp.maximum(amax, 1e-8) / 127.0).astype(w.dtype)
    sf = scale.astype(jnp.float32)
    q = jnp.clip(jnp.round(wf / sf), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize(w: Any) -> jax.Array:
    if not is_quantized(w):
        return w
    return w["q"].astype(w["s"].dtype) * w["s"]


def matmul(x: jax.Array, w: Any) -> jax.Array:
    """``x @ w`` for a plain or quantized weight.

    The int8→dtype cast sits inside the contraction, so XLA reads int8 from
    HBM and widens in registers; the per-channel scale applies to the
    (much smaller) output.
    """
    if not is_quantized(w):
        return x @ w
    y = x @ w["q"].astype(x.dtype)
    return y * jnp.squeeze(w["s"], axis=-2)


def expert_einsum(subscripts: str, x: jax.Array, w: Any) -> jax.Array:
    """Einsum against stacked MoE expert weights [E, in, out], plain or
    int8 (models/moe.py).  The per-(expert, output-channel) scale
    s [E, 1, out] folds into the (small) output: directly when the output
    is expert-major ("...->ecf"/"...->ech", capacity dispatch) and with
    the kept contract dim squeezed when the batch leads ("...->bef"/
    "...->beh", decode's all-expert pass) — trailing-dim broadcasting
    covers both."""
    if not is_quantized(w):
        return jnp.einsum(subscripts, x, w)
    y = jnp.einsum(subscripts, x, w["q"].astype(x.dtype))
    s = w["s"]
    if subscripts.split("->")[1][0] == "e":
        return y * s                          # [E, C, out] × [E, 1, out]
    return y * jnp.squeeze(s, axis=-2)        # [B, E, out] × [E, out]


def embed_rows(embed: Any, tokens: jax.Array) -> jax.Array:
    """Embedding-table row lookup for a plain or quantized table [V, H].

    Quantized tables carry PER-ROW scales (s [V, 1]): each token's row has
    its own dynamic range, so rare small-norm tokens keep full int8
    resolution instead of being crushed by a column-wide max."""
    with jax.named_scope("embed"):
        if not is_quantized(embed):
            return embed[tokens]
        return (embed["q"][tokens].astype(embed["s"].dtype)
                * embed["s"][tokens])


def tied_head(embed: Any, hidden: jax.Array) -> jax.Array:
    """``hidden @ embed.T`` (tied LM head) for plain or quantized table.

    With row scales s[V, 1]: hidden @ (q·s).T == (hidden @ q.T) · s.T —
    the scale folds into the [.., V] logits output, keeping the big matmul
    int8-read."""
    if not is_quantized(embed):
        return (hidden @ embed.T).astype(jnp.float32)
    logits = (hidden @ embed["q"].T.astype(hidden.dtype)).astype(jnp.float32)
    return logits * embed["s"][:, 0].astype(jnp.float32)


# Leaves quantized in a transformer params tree; norms stay full precision
# (tiny, and rsqrt precision matters).
_QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                     # the latent family's (models/latent_moe.py): its
                     # attention projections, shared and routed experts
                     "w_qa", "w_qb", "w_kva", "w_kvb", "ws_gate", "ws_up",
                     "ws_down", "we_gate", "we_up", "we_down",
                     # the hybrid family's state-space projections
                     # (models/hybrid_ssm.py); its conv, time-step and
                     # decay parameters and its router stay as made
                     "w_in", "w_out",
                     # the shared-K/V family's (models/shared_kv_hybrid.py):
                     # q|k|v side by side, the memory units' two and the
                     # gated MLP's; its time-step projections (w_x, w_dt)
                     # stay as made, with the conv and the decay
                     "w_qkv", "w_g1", "w_g2", "w1", "w2")


def maybe_quantize(params: Dict[str, Any], tier, cfg,
                   mesh=None) -> Dict[str, Any]:
    """Apply a tier's quantize mode with central validation — the one
    entry point every engine uses, so modes and support guards can't
    drift.  Unknown modes raise.  Dense and MoE families both quantize,
    sharded or not: on a tensor-parallel submesh the quantized tree is
    placed by the quantized sharding rules
    (parallel/sharding.quantized_param_shardings), so a tp tier streams
    half the weight bytes PER CHIP — decode is weight-bandwidth-bound,
    which is the entire point of int8 serving.
    """
    mode = getattr(tier, "quantize", "none")
    if mode == "none":
        return params
    if mode != "int8":
        raise ValueError(f"unknown quantize mode {mode!r} "
                         "(expected 'none' or 'int8')")
    if mesh is not None:
        from ..parallel.sharding import quantized_param_shardings
        shardings = quantized_param_shardings(cfg, mesh)
        return jax.jit(quantize_params, out_shardings=shardings)(params)
    return jax.jit(quantize_params)(params)


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize a transformer params tree (dense OR MoE) for serving.

    Matmul weights, stacked expert weights ([L, E, in, out] — per-(expert,
    channel) scales), and the (tied) embedding table go int8; norm gains
    and the tiny MoE router pass through.  Idempotent on already-quantized
    trees.
    """
    out = dict(params)
    if not is_quantized(params["embed"]):
        # Per-ROW scales for the embedding table (see embed_rows/tied_head).
        out["embed"] = quantize_tensor(params["embed"], contract_axis=-1)
    if "head" in params and not is_quantized(params["head"]):
        out["head"] = quantize_tensor(params["head"], contract_axis=-1)
    def stack(layers):
        layers = dict(layers)
        for k in _QUANT_LAYER_KEYS:
            if k in layers and not is_quantized(layers[k]):
                layers[k] = quantize_tensor(layers[k])
        return layers

    # "lead": the latent family's dense lead layers, a second stack (the
    # hybrid family's: a list, a layer a sublayer ahead of its loop);
    # "periods": the hybrid family's, a stack a position of its period.
    for group in ("layers", "lead", "periods"):
        if isinstance(params.get(group), list):
            out[group] = [stack(lp) for lp in params[group]]
        elif group in params:
            out[group] = stack(params[group])
    # "segments": the shared-K/V family's, a list of such periods.
    if "segments" in params:
        out["segments"] = [[stack(lp) for lp in seg]
                           for seg in params["segments"]]
    return out
