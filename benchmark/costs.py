"""Bytes of a decode step, from the configuration's sizes alone.

These are the numerators of every roofline share the benchmark reports;
they live here so that no PR that claims a gain can change them, and
``tests/test_costs.py`` holds them to hand-worked sizes for both models.
A share over 100% means a count here is too high or a time leaves out
part of the work — never clamp it.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


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
