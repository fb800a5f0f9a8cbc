"""What the harness knows of the latent-attention, routed-expert,
multi-stream decoder family (Xing4.0-29B-A4B's block): multi-head latent
attention over one cached row a position, leading dense SwiGLU layers,
then layers of a shared expert plus dropless top-k routed experts, a
residual of ``hc_mult`` streams mixed by doubly-stochastic maps, an
output head of its own.

Two things, both from the configuration's published keys alone: the
program's ``ModelConfig`` (with this family's checks and its rule for
rehearsal sizes), and the bytes of a decode step — the numerators of
every roofline share the benchmark reports for a tier of this family
(``costs.py`` finds them by the tier's ``family``;
``tests/test_costs_latent_moe.py`` holds them to hand-worked sizes).
The plain forward pass is ``reference/latent_moe_decoder.py``, which
takes nothing from here.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from costs import BYTES

# What the program's block assumes of the published keys; any other value
# is a different architecture, refused by name.
EXPECTED = {"hidden_act": "silu", "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "moe_layer_freq": 1,
            "attention_bias": False, "tie_word_embeddings": False,
            "ep_size": 1}


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes.  ``tokenizer``
    is the byte scheme so that any vocabulary size passes
    ``get_tokenizer``."""
    from distributed_llm_tpu.config import ModelConfig
    for key, want in EXPECTED.items():
        if model.get(key, want) != want:
            raise ValueError(f"{preset}: {key} = {model[key]!r}; the "
                             f"family's block is written for {want!r}")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError(f"{preset}: latent attention up-projects keys for "
                         f"every head; num_key_value_heads has to equal "
                         f"num_attention_heads")
    if model.get("num_nextn_predict_layers", 0):
        raise ValueError(f"{preset}: the multi-token-prediction block is "
                         f"not served; the configuration states "
                         f"num_nextn_predict_layers 0 under 'reduced'")
    if model["mhc_h_res_clamp_min"] != -model["mhc_h_res_clamp_max"]:
        raise ValueError(f"{preset}: the program clamps the stream map "
                         f"symmetrically")
    rs = model.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        raise ValueError(f"{preset}: rope_scaling type {rs.get('type')!r}; "
                         f"the program has YaRN")
    return ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        ffn_size=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model.get("rope_theta", 10000.0)),
        norm_eps=float(model.get("rms_norm_eps", 1e-6)),
        dtype=model.get("torch_dtype", "bfloat16"),
        tie_embeddings=False,
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_factor=float(rs.get("factor", 1.0)),
        rope_original_max_pos=int(
            rs.get("original_max_position_embeddings", 0)),
        rope_beta_fast=float(rs.get("beta_fast", 32)),
        rope_beta_slow=float(rs.get("beta_slow", 1)),
        rope_mscale=float(rs.get("mscale", 1.0)),
        rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        dense_lead_layers=model["first_k_dense_replace"],
        num_experts=model["n_routed_experts"],
        moe_ffn_size=model["moe_intermediate_size"],
        experts_per_token=model["num_experts_per_tok"],
        shared_experts=model["n_shared_experts"],
        router_scale=float(model["routed_scaling_factor"]),
        residual_streams=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"],
        hc_eps=float(model["hc_eps"]),
        hc_clamp=float(model["mhc_h_res_clamp_max"]))


def rehearsal_model(model: Dict[str, Any], sizes: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The published keys with the configuration's tiny CPU ``sizes`` in
    place; nothing of this family follows from another size."""
    return {**model, **sizes}


def attention_params(model: Dict[str, Any]) -> int:
    """Matrix parameters of one layer's latent attention."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, dc, dq = (model["v_head_dim"], model["kv_lora_rank"],
                  model["q_lora_rank"])
    return (h * dq + dq * nh * (dn + dr) + h * (dc + dr)
            + dc * nh * (dn + dv) + nh * dv * h)


def stream_map_params(model: Dict[str, Any]) -> int:
    """The two sublayers' stream maps of one layer."""
    n = model["hc_mult"]
    return 2 * n * model["hidden_size"] * (2 * n + n * n)


def expert_params(model: Dict[str, Any]) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_layer_params(model: Dict[str, Any]) -> int:
    return (attention_params(model) + stream_map_params(model)
            + 3 * model["hidden_size"] * model["intermediate_size"])


def expert_layer_fixed_params(model: Dict[str, Any]) -> int:
    """An expert layer without its routed experts: attention, stream
    maps, the shared expert(s), the router."""
    return (attention_params(model) + stream_map_params(model)
            + model["n_shared_experts"] * expert_params(model)
            + model["hidden_size"] * model["n_routed_experts"])


def _layers(model: Dict[str, Any]):
    lead = model["first_k_dense_replace"]
    return lead, model["num_hidden_layers"] - lead


def embed_params(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["hidden_size"]


def _one_chip(tp: int) -> None:
    if tp != 1:
        raise ValueError("the latent family is served on one chip a "
                         "stage (tp 1): its pool has no heads to shard on")


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes the chip HOLDS: every layer with all its experts,
    the embedding and the head.  A decode step reads less:
    ``decode_step_bytes_per_chip``."""
    _one_chip(tp)
    lead, moe = _layers(model)
    params = (lead * dense_layer_params(model)
              + moe * (expert_layer_fixed_params(model)
                       + model["n_routed_experts"] * expert_params(model))
              + 2 * embed_params(model))
    return params * BYTES[model.get("torch_dtype", "bfloat16")]


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """The one cached row of a position (latent + rotary key) over all
    layers, in the served dtype."""
    return (model["num_hidden_layers"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * BYTES[model.get("torch_dtype", "bfloat16")])


def expected_experts_touched(model: Dict[str, Any], batch: int) -> float:
    """Distinct experts of one layer that a step of ``batch`` tokens
    reads, EXPECTED at uniform routing: each expert is missed by a token
    with probability 1 - k/E.  A prediction; the program counts what it
    touched (``moe.experts_touched_per_step.nano``)."""
    e, k = model["n_routed_experts"], model["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** batch)


def decode_step_bytes_per_chip(model: Dict[str, Any],
                               contexts: Sequence[float], tp: int = 1,
                               experts_touched: Optional[float] = None
                               ) -> float:
    """The least one chip must read for one decode step of a batch whose
    sequences hold ``contexts`` positions: every non-expert matrix of the
    layers and the head once (not the embedding: one row a token), the
    routed experts the step's tokens chose, and the cached row of every
    position.  ``experts_touched`` is the distinct experts a layer the
    program COUNTED (``layer_metrics/moe_readers.py`` passes it: the
    numerator of ``step.decode_hbm_share_counted.nano``); without it the
    EXPECTED number at uniform routing, ``E (1 - (1 - k/E)^B)`` for
    ``B = len(contexts)``: a prediction, not a count — a skewed router
    reads fewer experts in less time, and a share over these bytes then
    overstates (99.9 % at 14 touched where 25.8 were assumed: my chip
    run, PR 29)."""
    _one_chip(tp)
    b = BYTES[model.get("torch_dtype", "bfloat16")]
    lead, moe = _layers(model)
    fixed = (lead * dense_layer_params(model)
             + moe * expert_layer_fixed_params(model)
             + embed_params(model))
    if experts_touched is None:
        experts_touched = expected_experts_touched(model, len(contexts))
    experts = moe * experts_touched * expert_params(model)
    return (fixed + experts) * b + sum(contexts) * kv_bytes_per_token(model)
