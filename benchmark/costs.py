"""Bytes of a decode step, from the configuration's sizes alone.

These are the numerators of every roofline share the benchmark reports;
they live under ``benchmark/`` so that no PR that claims a gain can
change them.  What is counted depends on the model family (a dense layer,
a latent cache, routed experts), so each count is the family's own,
``families/<family>.py``, found by the tier's ``family``; the names here
are the one way to them.  ``family`` defaults to the family these names
counted before there were several: a reader passes the tier's own.
``tests/test_costs.py`` holds the counts to hand-worked sizes for two
models.  A share over 100% means a count is too high or a time leaves
out part of the work — never clamp it.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import manifest as mf

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}
DENSE = "dense_decoder"


def layer_params(model: Dict[str, Any]) -> int:
    """Matrix parameters of one layer of the dense family."""
    return mf.load_family(DENSE).layer_params(model)


def embed_params(model: Dict[str, Any]) -> int:
    return mf.load_family(DENSE).embed_params(model)


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1,
                          family: str = DENSE) -> int:
    """Weight bytes one chip reads in one decode step."""
    return mf.load_family(family).weight_bytes_per_chip(model, tp)


def kv_bytes_per_token(model: Dict[str, Any], family: str = DENSE) -> int:
    """What one position keeps in the cache over all layers."""
    return mf.load_family(family).kv_bytes_per_token(model)


def decode_step_bytes_per_chip(model: Dict[str, Any],
                               contexts: Sequence[float], tp: int = 1,
                               family: str = DENSE) -> float:
    """The least one chip must read for one decode step of a batch whose
    sequences hold ``contexts`` positions."""
    return mf.load_family(family).decode_step_bytes_per_chip(model, contexts,
                                                             tp)
