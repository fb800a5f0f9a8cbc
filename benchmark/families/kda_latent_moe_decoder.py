"""What the harness knows of the delta-rule linear attention / latent
attention / routed-expert decoder (Moonshot's Kimi-Linear block,
``kimi_linear``): every layer a mixer and then a feed-forward on one
residual, RMSNorm before each, an output head of its own.  The mixer is
Kimi Delta Attention (``linear_attn_config.kda_layers``: a float32 matrix
state a head under a decay a CHANNEL, three short convs) or latent
attention without rotary (``full_attn_layers``: one cached row of
``kv_lora_rank + qk_rope_head_dim`` numbers a token); the feed-forward a
dense gated MLP in the first ``first_k_dense_replace`` layers and, after,
a shared expert plus dropless top-k sigmoid-routed gated experts, of which
this chip holds ONE SHARE: ``num_experts`` experts from
``first_routed_expert`` on, of the ``router_outputs`` the router scores.

Two things, both from the configuration's keys alone: the program's
``ModelConfig`` (a PATTERN of the program's hybrid row family, two
characters a layer: ``models/hybrid_ssm.py``) with this family's checks
and its rule for rehearsal sizes, and the bytes of a decode step by part —
the numerators of the shares the benchmark reports for a tier of this
family (``tests/test_costs_kda_latent_moe.py`` holds them to hand-worked
sizes).  The chunk recurrence and the step update are XLA products: the
family brings no kernel, so no kernel's operations are counted here.  The
plain forward pass is ``reference/kda_latent_moe_decoder.py``, which takes
nothing from here.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from costs import BYTES

# What the program's block assumes of the published keys; any other value
# is a different architecture, refused by name.  One expert group, so the
# grouped top-k is the plain one.
EXPECTED = {"model_type": "kimi_linear", "hidden_act": "silu",
            "mla_use_nope": True, "q_lora_rank": None,
            "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
            "moe_layer_freq": 1, "num_expert_group": 1, "topk_group": 1,
            "num_nextn_predict_layers": 0, "rope_scaling": None,
            "tie_word_embeddings": False}


def pattern(preset: str, model: Dict[str, Any]) -> str:
    """One character a SUBLAYER, two a layer: ``K`` or ``L`` the mixer,
    ``-`` or ``E`` the feed-forward."""
    lin = model["linear_attn_config"]
    out = []
    for layer in range(1, model["num_hidden_layers"] + 1):
        if (layer in lin["full_attn_layers"]) == (layer in lin["kda_layers"]):
            raise ValueError(f"{preset}: layer {layer} has to be in ONE of "
                             f"linear_attn_config's kda_layers and "
                             f"full_attn_layers")
        out.append("L" if layer in lin["full_attn_layers"] else "K")
        out.append("-" if layer <= model["first_k_dense_replace"] else "E")
    return "".join(out)


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes: the hybrid row
    family's pattern of SUBLAYERS.  ``tokenizer`` is the byte scheme so
    that any vocabulary size passes ``get_tokenizer``."""
    from distributed_llm_tpu.config import ModelConfig
    from distributed_llm_tpu.models import hybrid_ssm
    if not set("KL") <= set(hybrid_ssm.KINDS):
        raise ValueError(
            f"{preset}: this program's hybrid family (kinds "
            f"{hybrid_ssm.KINDS!r}) has no linear-attention rows and no "
            f"latent attention beside them: it does not serve this family")
    for key, want in EXPECTED.items():
        if model.get(key, want) != want:
            raise ValueError(f"{preset}: {key} = {model[key]!r}; the "
                             f"family's block is written for {want!r}")
    first, held = model.get("first_routed_expert", 0), model["num_experts"]
    if not 0 <= first <= model["router_outputs"] - held:
        raise ValueError(f"{preset}: experts {first}..+{held} are not "
                         f"among the router's {model['router_outputs']}")
    lin = model["linear_attn_config"]
    kinds = pattern(preset, model)
    return ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=len(kinds), layer_pattern=kinds,
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        rotary=False,
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        ffn_size=model["intermediate_size"],
        max_seq_len=model["model_max_length"],
        norm_eps=float(model["rms_norm_eps"]),
        dtype=model.get("torch_dtype", "bfloat16"),
        tie_embeddings=False,
        ssm_heads=lin["num_heads"], ssm_head_dim=lin["head_dim"],
        ssm_conv=lin["short_conv_kernel_size"],
        num_experts=model["router_outputs"],
        experts_first=first, experts_count=held,
        moe_ffn_size=model["moe_intermediate_size"],
        shared_ffn_size=(model["moe_intermediate_size"]
                         * model["num_shared_experts"]),
        experts_per_token=model["num_experts_per_token"],
        router_scale=float(model["routed_scaling_factor"]),
        expert_act="swiglu")


def rehearsal_model(model: Dict[str, Any], sizes: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The published keys with the configuration's tiny CPU ``sizes`` in
    place; ``linear_attn_config`` is replaced whole where they give one."""
    return {**model, **sizes}


# -- parameters, a sublayer of each kind ---------------------------------------

def _kda(model):
    """(heads, head size, taps, inner width)."""
    lin = model["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"],
            lin["num_heads"] * lin["head_dim"])


def kda_mixer_params(model: Dict[str, Any]) -> int:
    """One ``K`` mixer: the q, k, v and output projections, the decay's
    and the gate's low-rank pairs (hidden -> head size -> inner), beta's
    projection a head, the three convs' taps, ``A_log`` a head,
    ``dt_bias`` a channel and the output norm's gain over one head."""
    h = model["hidden_size"]
    nh, d, taps, inner = _kda(model)
    return (4 * h * inner + 2 * (h * d + d * inner) + h * nh
            + 3 * taps * inner + nh + inner + d)


def latent_mixer_params(model: Dict[str, Any]) -> int:
    """One ``L`` mixer: W_q by heads of nope + rope, W_kva to the cached
    row, the latent's norm gain, W_kvb to nope + value by heads, W_o."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    dc, dn, dr, dv = (model["kv_lora_rank"], model["qk_nope_head_dim"],
                      model["qk_rope_head_dim"], model["v_head_dim"])
    return (h * nh * (dn + dr) + h * (dc + dr) + dc + dc * nh * (dn + dv)
            + nh * dv * h)


def expert_params(model: Dict[str, Any]) -> int:
    """One routed expert, gated: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_expert_params(model: Dict[str, Any]) -> int:
    return model["num_shared_experts"] * expert_params(model)


def router_params(model: Dict[str, Any]) -> int:
    """The router over ALL its outputs, with the choice-only bias."""
    return (model["hidden_size"] + 1) * model["router_outputs"]


def lead_mlp_params(model: Dict[str, Any]) -> int:
    """A lead layer's dense gated MLP."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def embed_params(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["hidden_size"]


def _counts(model):
    """(K mixers, L mixers, dense MLPs, expert sublayers)."""
    kinds = pattern(model.get("name", "model"), model)
    return tuple(kinds.count(kind) for kind in "KL-E")


def norm_params(model: Dict[str, Any]) -> int:
    """A pre-norm gain a sublayer and the final norm's."""
    return (2 * model["num_hidden_layers"] + 1) * model["hidden_size"]


def _one_chip(tp: int) -> None:
    if tp != 1:
        raise ValueError("the hybrid family is served on one chip a share "
                         "(tp 1): its recurrent rows have no shards")


def _itemsize(model) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def held_params(model: Dict[str, Any]) -> int:
    """Every parameter the chip HOLDS: each sublayer of the stage with the
    experts of its share, the norms, the embedding and the head."""
    k, l, dense, e = _counts(model)
    return (k * kda_mixer_params(model) + l * latent_mixer_params(model)
            + dense * lead_mlp_params(model)
            + e * (router_params(model) + shared_expert_params(model)
                   + model["num_experts"] * expert_params(model))
            + norm_params(model) + 2 * embed_params(model))


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes the chip HOLDS, at the served dtype's size (the few
    float32 vectors — ``A_log``, ``dt_bias``, the router bias: 36 K
    numbers a layer — counted at it too).  A decode step reads less:
    ``decode_step_bytes_per_chip``."""
    _one_chip(tp)
    return held_params(model) * _itemsize(model)


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """The one latent row of a position over the ``L`` layers, in the
    served dtype; the ``K`` layers keep nothing by position."""
    return (_counts(model)[1]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * _itemsize(model))


def state_bytes_per_slot(model: Dict[str, Any]) -> int:
    """What a sequence keeps beside its latent rows whatever its length: a
    ``K`` layer the float32 matrix a head (heads x d_k x d_v) and the
    three convs' last taps - 1 input rows in the served dtype."""
    nh, d, taps, inner = _kda(model)
    return _counts(model)[0] * (nh * d * d * 4
                                + (taps - 1) * 3 * inner * _itemsize(model))


def expected_experts_touched(model: Dict[str, Any], batch: int) -> float:
    """Distinct HELD experts of one layer that a step of ``batch`` tokens
    reads, EXPECTED at uniform routing over all the router's outputs: a
    token misses an expert with probability 1 - k/E.  A prediction; the
    program counts what it touched
    (``moe.held_experts_touched_per_step.nano``)."""
    e, k = model["router_outputs"], model["num_experts_per_token"]
    return model["num_experts"] * (1.0 - (1.0 - k / e) ** batch)


def decode_step_parts(model: Dict[str, Any], contexts: Sequence[float],
                      experts_touched: Optional[float] = None
                      ) -> Dict[str, float]:
    """The least one chip must move for one decode step of a batch whose
    sequences hold ``contexts`` positions, by part: the linear-attention
    mixers' matrices, the latent mixers', the routers, the lead MLP, the
    shared experts, the held routed experts the step's tokens chose
    (``experts_touched`` a layer as the program COUNTED them; without it
    the expectation at uniform routing: a prediction), the norms and the
    head once (not the embedding: one row a token), the recurrent rows of
    every sequence READ AND WRITTEN, and the latent rows of every
    position."""
    b = _itemsize(model)
    k, l, dense, e = _counts(model)
    if experts_touched is None:
        experts_touched = expected_experts_touched(model, len(contexts))
    return {
        "kda_mixers": k * kda_mixer_params(model) * b,
        "latent_mixers": l * latent_mixer_params(model) * b,
        "routers": e * router_params(model) * b,
        "lead_mlp": dense * lead_mlp_params(model) * b,
        "experts_shared": e * shared_expert_params(model) * b,
        "experts_routed": e * experts_touched * expert_params(model) * b,
        "norms": norm_params(model) * b,
        "head": embed_params(model) * b,
        "state": 2 * len(contexts) * state_bytes_per_slot(model),
        "kv": sum(contexts) * kv_bytes_per_token(model),
    }


def decode_step_bytes_per_chip(model: Dict[str, Any],
                               contexts: Sequence[float], tp: int = 1,
                               experts_touched: Optional[float] = None
                               ) -> float:
    """The sum of ``decode_step_parts``."""
    _one_chip(tp)
    return float(sum(decode_step_parts(model, contexts,
                                       experts_touched).values()))
