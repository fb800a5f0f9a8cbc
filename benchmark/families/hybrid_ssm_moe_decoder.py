"""What the harness knows of the state-space / attention / routed-expert
hybrid decoder family (NVIDIA-Nemotron-3-Nano-30B-A3B's block,
``nemotron_h``): a pattern of layers each ONE pre-norm mixer — ``M`` a
Mamba-2 state-space mixer, ``*`` grouped-query attention with no rotary
embedding, ``E`` a shared expert plus dropless top-k routed non-gated
(``relu^2``) experts — an output head of its own, and ONE CHIP'S SHARE of
each expert layer: ``n_routed_experts`` experts from
``first_routed_expert`` on, of the ``router_outputs`` the router scores.

Two things, both from the configuration's published keys alone: the
program's ``ModelConfig`` (with this family's checks and its rule for
rehearsal sizes), and the bytes of a decode step — the numerators of the
roofline shares the benchmark reports for a tier of this family
(``costs.py`` finds them by the tier's ``family``;
``tests/test_costs_hybrid_ssm_moe.py`` holds them to hand-worked sizes).
The plain forward pass is ``reference/hybrid_ssm_moe_decoder.py``, which
takes nothing from here.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from costs import BYTES

# What the program's block assumes of the published keys; any other value
# is a different architecture, refused by name.
EXPECTED = {"model_type": "nemotron_h", "mamba_hidden_act": "silu",
            "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "attention_bias": False,
            "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
            "use_conv_bias": True, "tie_word_embeddings": False,
            "residual_in_fp32": False, "n_shared_experts": 1,
            "sliding_window": None}
KINDS = "M*E"


def _pattern(preset: str, model: Dict[str, Any]) -> str:
    pattern = model["hybrid_override_pattern"]
    if (len(pattern) != model["num_hidden_layers"]
            or set(pattern) - set(KINDS)):
        raise ValueError(f"{preset}: hybrid_override_pattern {pattern!r} "
                         f"has to be num_hidden_layers = "
                         f"{model['num_hidden_layers']} characters of "
                         f"{KINDS!r}")
    return pattern


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes.  ``tokenizer``
    is the byte scheme so that any vocabulary size passes
    ``get_tokenizer``."""
    from distributed_llm_tpu.config import ModelConfig
    for key, want in EXPECTED.items():
        if model.get(key, want) != want:
            raise ValueError(f"{preset}: {key} = {model[key]!r}; the "
                             f"family's block is written for {want!r}")
    if model["norm_eps"] != model.get("layer_norm_epsilon",
                                      model["norm_eps"]):
        raise ValueError(f"{preset}: norm_eps and layer_norm_epsilon "
                         f"differ; the program has one")
    first, held = model.get("first_routed_expert", 0), \
        model["n_routed_experts"]
    if not 0 <= first <= model["router_outputs"] - held:
        raise ValueError(f"{preset}: experts {first}..+{held} are not "
                         f"among the router's {model['router_outputs']}")
    return ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        layer_pattern=_pattern(preset, model),
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        rotary=False,
        ffn_size=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["norm_eps"]),
        dtype=model.get("torch_dtype", "bfloat16"),
        tie_embeddings=False,
        ssm_heads=model["mamba_num_heads"],
        ssm_head_dim=model["mamba_head_dim"],
        ssm_state=model["ssm_state_size"],
        ssm_groups=model["n_groups"],
        ssm_conv=model["conv_kernel"],
        ssm_dt_min=float(model["time_step_min"]),
        ssm_dt_max=float(model["time_step_max"]),
        ssm_dt_floor=float(model["time_step_floor"]),
        num_experts=model["router_outputs"],
        experts_first=first, experts_count=held,
        moe_ffn_size=model["moe_intermediate_size"],
        shared_ffn_size=model["moe_shared_expert_intermediate_size"],
        experts_per_token=model["num_experts_per_tok"],
        router_scale=float(model["routed_scaling_factor"]),
        expert_act="relu2")


def rehearsal_model(model: Dict[str, Any], sizes: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The published keys with the configuration's tiny CPU ``sizes`` in
    place; nothing of this family follows from another size."""
    return {**model, **sizes}


# -- parameters, a layer of each kind -----------------------------------------

def _ssm(model):
    """(inner width, conv channels) of a state-space layer: heads x head
    size (the published code reads that; ``expand`` goes unread), and x,
    B and C side by side."""
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    return inner, inner + 2 * model["n_groups"] * model["ssm_state_size"]


def ssm_layer_params(model: Dict[str, Any]) -> int:
    """One ``M`` layer: in and out projections, the depthwise conv with
    its bias, dt_bias, A_log and D a head, the gated norm's gain and the
    layer's pre-norm."""
    h, nh = model["hidden_size"], model["mamba_num_heads"]
    inner, conv = _ssm(model)
    return (h * (inner + conv + nh) + conv * model["conv_kernel"] + conv
            + 3 * nh + inner + inner * h + h)


def attention_layer_params(model: Dict[str, Any]) -> int:
    """One ``*`` layer: q and o by query heads, k and v by K/V heads, the
    pre-norm."""
    h, d = model["hidden_size"], model["head_dim"]
    return (2 * h * model["num_attention_heads"] * d
            + 2 * h * model["num_key_value_heads"] * d + h)


def expert_params(model: Dict[str, Any]) -> int:
    """One routed expert, non-gated: up and down."""
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_expert_params(model: Dict[str, Any]) -> int:
    return 2 * model["hidden_size"] \
        * model["moe_shared_expert_intermediate_size"]


def expert_layer_fixed_params(model: Dict[str, Any]) -> int:
    """An ``E`` layer without its routed experts: the shared expert, the
    router over all its outputs with its bias, the pre-norm."""
    return (shared_expert_params(model)
            + model["hidden_size"] * model["router_outputs"]
            + model["router_outputs"] + model["hidden_size"])


def embed_params(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["hidden_size"]


def _counts(model):
    pattern = _pattern(model.get("name", "model"), model)
    return tuple(pattern.count(kind) for kind in KINDS)


def _one_chip(tp: int) -> None:
    if tp != 1:
        raise ValueError("the hybrid family is served on one chip a share "
                         "(tp 1): its recurrent rows have no shards")


def _itemsize(model) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes the chip HOLDS: every layer with the experts of its
    share, the embedding and the head.  (The final norm's gain is not
    counted: ISSUE 33's hand-worked sum has none.)  A decode step reads
    less: ``decode_step_bytes_per_chip``."""
    _one_chip(tp)
    m, a, e = _counts(model)
    params = (m * ssm_layer_params(model) + a * attention_layer_params(model)
              + e * (expert_layer_fixed_params(model)
                     + model["n_routed_experts"] * expert_params(model))
              + 2 * embed_params(model))
    return params * _itemsize(model)


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """K and V of a position over the ATTENTION layers, in the served
    dtype; the state-space layers keep nothing by position."""
    return (_counts(model)[1] * 2 * model["num_key_value_heads"]
            * model["head_dim"] * _itemsize(model))


def state_bytes_per_slot(model: Dict[str, Any], layers: Optional[int] = None
                         ) -> int:
    """What a sequence keeps beside its K/V whatever its length: a layer
    the float32 state (heads x head size x state) and the conv's last
    taps - 1 input rows in the served dtype; over ``layers`` state-space
    layers (all of the pattern's by default)."""
    inner, conv = _ssm(model)
    if layers is None:
        layers = _counts(model)[0]
    return layers * (inner * model["ssm_state_size"] * 4
                     + (model["conv_kernel"] - 1) * conv * _itemsize(model))


def expected_experts_touched(model: Dict[str, Any], batch: int) -> float:
    """Distinct HELD experts of one layer that a step of ``batch`` tokens
    reads, EXPECTED at uniform routing over all the router's outputs: a
    token misses an expert with probability 1 - k/E.  A prediction; the
    program counts what it touched
    (``moe.held_experts_touched_per_step.nano``)."""
    e, k = model["router_outputs"], model["num_experts_per_tok"]
    return model["n_routed_experts"] * (1.0 - (1.0 - k / e) ** batch)


def decode_step_parts(model: Dict[str, Any], contexts: Sequence[float],
                      experts_touched: Optional[float] = None
                      ) -> Dict[str, float]:
    """The least one chip must move for one decode step of a batch whose
    sequences hold ``contexts`` positions, by part: every mixer matrix
    and the head once (not the embedding: one row a token), the shared
    experts and routers, the held routed experts the step's tokens chose
    (``experts_touched`` a layer as the program COUNTED them; without it
    the expectation at uniform routing: a prediction), the recurrent
    state of every sequence READ AND WRITTEN, and the K/V of every
    position."""
    b = _itemsize(model)
    m, a, e = _counts(model)
    if experts_touched is None:
        experts_touched = expected_experts_touched(model, len(contexts))
    return {
        "mixers": (m * ssm_layer_params(model)
                   + a * attention_layer_params(model)) * b,
        "experts_fixed": e * expert_layer_fixed_params(model) * b,
        "experts_routed": e * experts_touched * expert_params(model) * b,
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
