"""What the harness knows of the compressed-convolutional-attention /
top-1 routed-expert decoder (Zyphra's ZAYA1 block, ``zaya``): every layer
an attention sublayer and an expert sublayer on one residual, RMSNorm
before each and a scaled merge after, a tied head.  The attention lives in
the heads' own widths after three down-projections (two causal
convolutions of two taps over queries and keys side by side, the q-k mean,
a norm a head with a temperature a K/V head, rotary on part of a head, a
value shift); the experts are gated, ONE of ``num_experts`` a token, under
an MLP router whose state is carried from layer to layer.

Two things, both from the configuration's keys alone: the program's
``ModelConfig`` (a PATTERN of the program's hybrid row family, ``CE`` a
layer: ``models/hybrid_ssm.py``) with this family's checks and its rule
for rehearsal sizes, and the bytes of a decode step by part — the
numerators of the shares the benchmark reports for a tier of this family
(``tests/test_costs_cca_moe.py`` holds them to hand-worked sizes).  The
plain forward pass is ``reference/cca_moe_decoder.py``, which takes
nothing from here.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from costs import BYTES

# What the program's block assumes of the published keys; any other value
# is a different architecture, refused by name.  The two convolutions are
# written for two taps each: the tail row keeps ONE position.
EXPECTED = {"model_type": "zaya", "hidden_act": "silu",
            "attention_bias": False, "lm_head_bias": False,
            "tie_word_embeddings": True, "sliding_window": None,
            "cca_time0": 2, "cca_time1": 2}
LAYER_TYPE = "hybrid"


def _rope(model: Dict[str, Any]) -> Dict[str, Any]:
    """The rotary settings of the model's one layer type."""
    return model["rope_parameters"][LAYER_TYPE]


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes: the hybrid row
    family's pattern of SUBLAYERS, ``CE`` a layer.  ``tokenizer`` is the
    byte scheme so that any vocabulary size passes ``get_tokenizer``."""
    from distributed_llm_tpu.config import ModelConfig
    from distributed_llm_tpu.models import hybrid_ssm
    if "C" not in hybrid_ssm.KINDS or not hasattr(hybrid_ssm, "route_mlp"):
        raise ValueError(
            f"{preset}: this program's hybrid family (kinds "
            f"{hybrid_ssm.KINDS!r}) has no compressed convolutional "
            f"attention with a tail row a slot and no MLP router with a "
            f"carry: it does not serve this family")
    for key, want in EXPECTED.items():
        if model.get(key, want) != want:
            raise ValueError(f"{preset}: {key} = {model[key]!r}; the "
                             f"family's block is written for {want!r}")
    types = model["layer_types"]
    if len(types) != model["num_hidden_layers"] or set(types) != {LAYER_TYPE}:
        raise ValueError(f"{preset}: layer_types has to be "
                         f"num_hidden_layers = {model['num_hidden_layers']} "
                         f"x {LAYER_TYPE!r}")
    rope = _rope(model)
    if (rope.get("rope_type", "default") != "default"
            or rope["partial_rotary_factor"]
            != model["partial_rotary_factor"]):
        raise ValueError(f"{preset}: rope_parameters.{LAYER_TYPE} = {rope}; "
                         f"the block applies the plain rotary embedding to "
                         f"partial_rotary_factor of a head")
    pattern = "CE" * model["num_hidden_layers"]
    return ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=len(pattern), layer_pattern=pattern,
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"], rotary=True,
        qk_rope_head_dim=int(model["head_dim"]
                             * model["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]),
        dtype=model.get("torch_dtype", "bfloat16"),
        tie_embeddings=True,
        num_experts=model["num_experts"],
        experts_per_token=model["num_experts_per_tok"],
        moe_ffn_size=model["moe_intermediate_size"],
        router_hidden=model["router_hidden_size"],
        expert_act="swiglu")


def rehearsal_model(model: Dict[str, Any], sizes: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The published keys with the configuration's tiny CPU ``sizes`` in
    place; ``layer_types`` follows the rehearsal depth."""
    out = {**model, **sizes}
    out["layer_types"] = [LAYER_TYPE] * out["num_hidden_layers"]
    return out


# -- parameters, a sublayer of each kind ---------------------------------------

def _heads(model):
    """(query width, K/V width, head size)."""
    d = model["head_dim"]
    return (model["num_attention_heads"] * d,
            model["num_key_value_heads"] * d, d)


def attention_matrix_params(model: Dict[str, Any]) -> int:
    """The five projections: W_q, W_k, W_v1 and W_v2 (half the K/V width
    each), W_o."""
    h = model["hidden_size"]
    dq, dk, _ = _heads(model)
    return h * dq + h * dk + h * dk + dq * h


def conv_params(model: Dict[str, Any]) -> int:
    """The two convolutions over the dq + dk channels: the depthwise taps
    and the taps grouped by head (a D x D block a head a tap), each with
    its bias."""
    dq, dk, d = _heads(model)
    c = dq + dk
    return (model["cca_time0"] * c + c
            + model["cca_time1"] * (c // d) * d * d + c)


def router_params(model: Dict[str, Any]) -> int:
    """The down-projection, the MLP's two square layers and its outputs,
    the carry's gain, the norm's gain, the choice-only bias."""
    h, rh, e = (model["hidden_size"], model["router_hidden_size"],
                model["num_experts"])
    return h * rh + 2 * rh * rh + rh * e + 2 * rh + e


def expert_params(model: Dict[str, Any]) -> int:
    """One routed expert, gated: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def vector_bytes_per_layer(model: Dict[str, Any]) -> int:
    """A layer's small vectors: the two pre-norm gains and the
    temperature a K/V head in the served dtype's and float32's sizes, the
    two scaled merges' four float32 vectors each."""
    h = model["hidden_size"]
    return (2 * h * _itemsize(model) + model["num_key_value_heads"] * 4
            + 8 * h * 4)


def layer_params(model: Dict[str, Any]) -> int:
    """One layer's matrices and convolutions with every expert."""
    return (attention_matrix_params(model) + conv_params(model)
            + router_params(model)
            + model["num_experts"] * expert_params(model))


def embed_params(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["hidden_size"]


def _one_chip(tp: int) -> None:
    if tp != 1:
        raise ValueError("the hybrid family is served on one chip a stage "
                         "(tp 1): its tail rows have no shards")


def _itemsize(model) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes the chip HOLDS: every layer of the stage with all its
    experts, and the tied table once.  A decode step reads less:
    ``decode_step_bytes_per_chip``."""
    _one_chip(tp)
    n = model["num_hidden_layers"]
    return ((n * layer_params(model) + embed_params(model))
            * _itemsize(model) + n * vector_bytes_per_layer(model))


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """K (as attended) and V of a position over every layer, in the
    served dtype: the K/V width twice a layer."""
    return (model["num_hidden_layers"] * 2 * _heads(model)[1]
            * _itemsize(model))


def tail_bytes_per_slot(model: Dict[str, Any]) -> int:
    """What a sequence keeps beside its K/V whatever its length: a layer
    the last token's input to each convolution (dq + dk channels each)
    and the value the shifted half of the K/V heads takes from it."""
    dq, dk, _ = _heads(model)
    return (model["num_hidden_layers"] * (2 * (dq + dk) + dk // 2)
            * _itemsize(model))


def expected_experts_touched(model: Dict[str, Any], batch: int) -> float:
    """Distinct experts of one layer that a step of ``batch`` tokens
    reads, EXPECTED at uniform routing: a token misses an expert with
    probability 1 - k/E.  A prediction; the program counts what it
    touched (``moe.top1_experts_touched_per_step.nano``)."""
    e, k = model["num_experts"], model["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** batch)


def decode_step_parts(model: Dict[str, Any], contexts: Sequence[float],
                      experts_touched: Optional[float] = None
                      ) -> Dict[str, float]:
    """The least one chip must move for one decode step of a batch whose
    sequences hold ``contexts`` positions, by part: every attention
    sublayer's projections and convolutions, every router, the small
    vectors and the head once (not the embedding: one row a token), the
    experts the step's tokens chose (``experts_touched`` a layer as the
    program COUNTED them; without it the expectation at uniform routing:
    a prediction), the tail row of every sequence READ AND WRITTEN, and
    the K/V of every position."""
    b = _itemsize(model)
    n = model["num_hidden_layers"]
    if experts_touched is None:
        experts_touched = expected_experts_touched(model, len(contexts))
    return {
        "attention": n * (attention_matrix_params(model)
                          + conv_params(model)) * b,
        "routers": n * router_params(model) * b,
        "vectors": n * vector_bytes_per_layer(model),
        "experts_routed": n * experts_touched * expert_params(model) * b,
        "head": embed_params(model) * b,
        "tail": 2 * len(contexts) * tail_bytes_per_slot(model),
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
