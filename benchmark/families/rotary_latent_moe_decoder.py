"""What the harness knows of the rotary latent attention / routed-expert
decoder (Sarvam's block, ``sarvam_mla``): every layer latent attention and
then a feed-forward on one plain residual, RMSNorm before each, an output
head of its own.  Latent attention caches ONE row of ``kv_lora_rank +
qk_rope_head_dim`` numbers a token a layer, the last ``qk_rope_head_dim``
rotated by YaRN's frequencies (``rope_scaling``, ``deepseek_yarn``), and
queries by one matrix (no ``q_lora_rank``); the feed-forward is a dense
gated MLP in the first ``first_k_dense_replace`` layers and, after, a
shared expert plus dropless top-k sigmoid-routed gated experts, of which
this chip holds ONE SHARE: ``num_experts`` experts from
``first_routed_expert`` on, of the ``router_outputs`` the router scores.

Two things, both from the configuration's keys alone: the program's
``ModelConfig`` (a PATTERN of the program's hybrid row family with no row
kind in it, two characters a layer: ``models/hybrid_ssm.py``) with this
family's checks and its rule for rehearsal sizes; and what a step costs
by part — the bytes of a decode step and the matrix products of a chunk
program, the numerators of the shares the benchmark reports for a tier of
this family (``tests/test_costs_rotary_latent_moe.py`` holds them to
hand-worked sizes).  The family brings no kernel.  The plain forward pass
is ``reference/rotary_latent_moe_decoder.py``, which takes nothing from
here.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from costs import BYTES

# What the program's block assumes of the published keys; any other value
# is a different architecture, refused by name.
EXPECTED = {"model_type": "sarvam_mla", "hidden_act": "silu",
            "moe_router_enable_expert_bias": True, "use_qk_norm": True,
            "tie_word_embeddings": False}


def pattern(preset: str, model: Dict[str, Any]) -> str:
    """One character a SUBLAYER, two a layer: ``L`` the mixer, ``-`` or
    ``E`` the feed-forward."""
    return "".join("L" + ("-" if layer <= model["first_k_dense_replace"]
                          else "E")
                   for layer in range(1, model["num_hidden_layers"] + 1))


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes: the hybrid row
    family's pattern of SUBLAYERS, ``L`` under a rotary term, checked at
    once (a program that cannot run the pattern says so here, before
    anything is built).  ``tokenizer`` is the byte scheme so that any
    vocabulary size passes ``get_tokenizer``."""
    from distributed_llm_tpu.config import ModelConfig
    from distributed_llm_tpu.models import hybrid_ssm
    rope = model["rope_scaling"]
    # The two keys that restate widths: a head's score width, and the
    # cached row's (``head_dim``, which no layer reads).
    want = {**EXPECTED, "rope_scaling": {**rope, "type": "deepseek_yarn"},
            "q_head_dim": (model["qk_nope_head_dim"]
                           + model["qk_rope_head_dim"]),
            "head_dim": model["kv_lora_rank"] + model["qk_rope_head_dim"]}
    for key, value in want.items():
        if model.get(key, value) != value:
            raise ValueError(f"{preset}: {key} = {model[key]!r}; the "
                             f"family's block is written for {value!r}")
    first, held = model.get("first_routed_expert", 0), model["num_experts"]
    if not 0 <= first <= model["router_outputs"] - held:
        raise ValueError(f"{preset}: experts {first}..+{held} are not "
                         f"among the router's {model['router_outputs']}")
    kinds = pattern(preset, model)
    cfg = ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=len(kinds), layer_pattern=kinds,
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_attention_heads"],
        rotary=True,
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max_pos=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        ffn_size=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]),
        dtype=model.get("torch_dtype", "bfloat16"),
        tie_embeddings=False,
        num_experts=model["router_outputs"],
        experts_first=first, experts_count=held,
        moe_ffn_size=model["moe_intermediate_size"],
        shared_ffn_size=(model["moe_intermediate_size"]
                         * model["num_shared_experts"]),
        experts_per_token=model["num_experts_per_tok"],
        router_scale=float(model["routed_scaling_factor"]),
        expert_act="swiglu")
    hybrid_ssm.check(cfg)
    return cfg


def rehearsal_model(model: Dict[str, Any], sizes: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The published keys with the configuration's tiny CPU ``sizes`` in
    place; ``rope_scaling`` is replaced whole where they give one."""
    return {**model, **sizes}


# -- parameters, a sublayer of each kind ---------------------------------------

def _mla(model):
    """(heads, latent, nope, rope, value) widths."""
    return (model["num_attention_heads"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"])


def latent_mixer_params(model: Dict[str, Any]) -> int:
    """One ``L`` mixer: W_q by heads of nope + rope, W_kva to the cached
    row, the latent's norm gain, W_kvb to nope + value by heads, W_o."""
    h = model["hidden_size"]
    nh, dc, dn, dr, dv = _mla(model)
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
    """(L mixers, dense MLPs, expert sublayers)."""
    kinds = pattern(model.get("name", "model"), model)
    return tuple(kinds.count(kind) for kind in "L-E")


def norm_params(model: Dict[str, Any]) -> int:
    """A pre-norm gain a sublayer and the final norm's."""
    return (2 * model["num_hidden_layers"] + 1) * model["hidden_size"]


def _one_chip(tp: int) -> None:
    if tp != 1:
        raise ValueError("the hybrid family is served on one chip a share "
                         "(tp 1): its rows have no shards")


def _itemsize(model) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def held_params(model: Dict[str, Any]) -> int:
    """Every parameter the chip HOLDS: each sublayer of the stage with the
    experts of its share, the norms, the embedding and the head."""
    l, dense, e = _counts(model)
    return (l * latent_mixer_params(model) + dense * lead_mlp_params(model)
            + e * (router_params(model) + shared_expert_params(model)
                   + model["num_experts"] * expert_params(model))
            + norm_params(model) + 2 * embed_params(model))


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes the chip HOLDS, at the served dtype's size (the router
    bias, float32, counted at it too).  A decode step reads less:
    ``decode_step_bytes_per_chip``."""
    _one_chip(tp)
    return held_params(model) * _itemsize(model)


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """The one latent row of a position over the layers, in the served
    dtype: its NUMBERS (the pool rests a row at whole lane-widths, 640 for
    576, with zeros nothing reads)."""
    return (_counts(model)[0]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * _itemsize(model))


def expected_experts_touched(model: Dict[str, Any], batch: int) -> float:
    """Distinct HELD experts of one layer that a step of ``batch`` tokens
    reads, EXPECTED at uniform routing over all the router's outputs: a
    token misses an expert with probability 1 - k/E.  A prediction."""
    e, k = model["router_outputs"], model["num_experts_per_tok"]
    return model["num_experts"] * (1.0 - (1.0 - k / e) ** batch)


# -- one chunk program ---------------------------------------------------------

def chunk_loops(model: Dict[str, Any]) -> int:
    """``while`` loops one execution of the chunk program runs on the
    device: the lead layer inline, then one scan of the periods."""
    segments = model_config("loops", model).layer_segments
    return sum(1 for _, reps in segments if reps > 1)


def chunk_flops_per_chip(model: Dict[str, Any], steps: int, window: int
                         ) -> int:
    """Operations of the MATRIX PRODUCTS of one chunk program over
    ``steps`` positions that attends a window rung of ``window``
    positions (a multiply-add two).  A latent layer: the chunk's three
    projections, the up-projection of the WHOLE rung's latent rows to keys
    and values by heads, scores and values over the whole rung (masked
    positions are computed).  The lead MLP; an expert layer's router,
    shared expert and the routed experts this share holds at the
    assignments uniform routing sends them (``steps`` x k x held / E: a
    prediction; the program counts what came).  The head for the ONE
    position whose logits the program samples.  Norms, the rotation and
    the softmax are not matrix products and are not counted: a share of
    the chip's matrix-product peak."""
    h = model["hidden_size"]
    nh, dc, dn, dr, dv = _mla(model)
    l, dense, e = _counts(model)
    attention = (2 * steps * (h * nh * (dn + dr) + h * (dc + dr)
                              + nh * dv * h)
                 + 2 * window * dc * nh * (dn + dv)
                 + 2 * steps * window * nh * (dn + dr + dv))
    held = (steps * model["num_experts_per_tok"] * model["num_experts"]
            / model["router_outputs"])
    experts = (2 * steps * (h * model["router_outputs"]
                            + shared_expert_params(model))
               + 2 * held * expert_params(model))
    return int(l * attention + dense * 2 * steps * lead_mlp_params(model)
               + e * experts + 2 * embed_params(model))


# -- one decode step -------------------------------------------------------------

def decode_step_parts(model: Dict[str, Any], contexts: Sequence[float],
                      experts_touched: Optional[float] = None
                      ) -> Dict[str, float]:
    """The least one chip must move for one decode step of a batch whose
    sequences hold ``contexts`` positions, by part: the latent mixers'
    matrices (the absorbed form reads W_kvb once, never a position's keys
    or values), the routers, the lead MLP, the shared experts, the held
    routed experts the step's tokens chose (``experts_touched`` a layer as
    the program COUNTED them; without it the expectation at uniform
    routing: a prediction), the norms and the head once (not the
    embedding: one row a token), and the latent rows of every position."""
    b = _itemsize(model)
    l, dense, e = _counts(model)
    if experts_touched is None:
        experts_touched = expected_experts_touched(model, len(contexts))
    return {
        "latent_mixers": l * latent_mixer_params(model) * b,
        "routers": e * router_params(model) * b,
        "lead_mlp": dense * lead_mlp_params(model) * b,
        "experts_shared": e * shared_expert_params(model) * b,
        "experts_routed": e * experts_touched * expert_params(model) * b,
        "norms": norm_params(model) * b,
        "head": embed_params(model) * b,
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
