"""What the harness knows of the dense decoder family: RMSNorm, rotary
embeddings, (grouped-)multi-head attention with ``head_dim = hidden /
heads``, SwiGLU, the output head tied to the embedding.

Two things, both from the configuration's published keys alone: the
program's ``ModelConfig`` (with this family's checks and its rule for
rehearsal sizes), and the bytes of a decode step — the numerators of
every roofline share the benchmark reports for a tier of this family
(``costs.py`` finds them by the tier's ``family``;
``tests/test_costs.py`` holds them to hand-worked sizes for two models).
The plain forward pass is ``reference/dense_decoder.py``, which takes
nothing from here.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

from costs import BYTES


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes.  ``tokenizer``
    is the byte scheme so that any vocabulary size passes
    ``get_tokenizer``."""
    from distributed_llm_tpu.config import ModelConfig
    cfg = ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        ffn_size=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model.get("rope_theta", 10000.0)),
        norm_eps=float(model.get("rms_norm_eps", 1e-5)),
        dtype=model.get("torch_dtype", "bfloat16"))
    if cfg.head_dim != model.get("head_dim", cfg.head_dim):
        raise ValueError(f"{preset}: head_dim {model['head_dim']} is not "
                         f"hidden/heads = {cfg.head_dim}")
    return cfg


def rehearsal_model(model: Dict[str, Any], sizes: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The published keys with the configuration's tiny CPU ``sizes`` in
    place; this family's ``head_dim`` follows hidden and heads."""
    model = {**model, **sizes}
    model["head_dim"] = model["hidden_size"] // model["num_attention_heads"]
    return model


def _dims(model: Dict[str, Any]):
    h = model["hidden_size"]
    nq = model["num_attention_heads"]
    nkv = model["num_key_value_heads"]
    d = model.get("head_dim") or h // nq
    return (h, nq, nkv, d, model["intermediate_size"],
            model["num_hidden_layers"], model["vocab_size"])


def layer_params(model: Dict[str, Any]) -> int:
    """Matrix parameters of one decoder layer (norm gains left out)."""
    h, nq, nkv, d, f, _, _ = _dims(model)
    return h * nq * d + 2 * h * nkv * d + nq * d * h + 3 * h * f


def embed_params(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["hidden_size"]


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes one chip reads in one decode step: its 1/tp share of
    every layer matrix, and the whole embedding once as the tied output
    head (held whole on every chip of a tensor-parallel tier)."""
    b = BYTES[model.get("torch_dtype", "bfloat16")]
    layers = model["num_hidden_layers"] * layer_params(model)
    return (layers // tp + embed_params(model)) * b


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """K and V of one position over all layers, in the served dtype."""
    _, _, nkv, d, _, n_layers, _ = _dims(model)
    return 2 * n_layers * nkv * d * BYTES[model.get("torch_dtype",
                                                    "bfloat16")]


def decode_step_bytes_per_chip(model: Dict[str, Any],
                               contexts: Sequence[float],
                               tp: int = 1) -> float:
    """The least one chip must read for one decode step of a batch whose
    sequences hold ``contexts`` positions: its weights once, and its share
    of every sequence's K/V."""
    kv = sum(contexts) * kv_bytes_per_token(model) / tp
    return weight_bytes_per_chip(model, tp) + kv
