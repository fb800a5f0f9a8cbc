"""What the harness knows of the state-space / window-attention /
shared-K/V decoder family (Phi-4-mini-flash-reasoning's block,
``phi4flash``, a "decoder-hybrid-decoder"): a pattern of layers, each a
mixer and then a gated MLP, LayerNorm with bias, a tied head — ``M`` a
Mamba-1 mixer, ``W`` attention over a window of ``sliding_window``
positions, ``F`` ONE full-attention layer whose K/V are the only K/V
cached by position, ``X`` attention with a query projection only over
``F``'s K/V, ``G`` a gated memory unit over the last ``M`` layer's scan
output; differential heads in every attention layer.

Two things, both from the configuration's keys alone: the program's
``ModelConfig`` (with this family's checks and its rule for rehearsal
sizes), and the bytes of a decode step — the numerators of the roofline
shares the benchmark reports for a tier of this family (``costs.py``
finds them by the tier's ``family``;
``tests/test_costs_ssm_window_shared_kv.py`` holds them to hand-worked
sizes).  The plain forward pass is
``reference/ssm_window_shared_kv_decoder.py``, which takes nothing from
here.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

from costs import BYTES

# What the program's block assumes of the published keys; any other value
# is a different architecture, refused by name.
EXPECTED = {"model_type": "phi4flash", "hidden_act": "silu",
            "tie_word_embeddings": True, "mlp_bias": False,
            "lm_head_bias": False, "mb_per_layer": 2}
KINDS = "MWFGX"


def _pattern(preset: str, model: Dict[str, Any]) -> str:
    pattern = model["layer_pattern"]
    if (len(pattern) != model["num_hidden_layers"]
            or set(pattern) - set(KINDS)):
        raise ValueError(f"{preset}: layer_pattern {pattern!r} has to be "
                         f"num_hidden_layers = "
                         f"{model['num_hidden_layers']} characters of "
                         f"{KINDS!r}")
    return pattern


def _inner(model) -> int:
    return model["mamba_expand"] * model["hidden_size"]


def _head_dim(model) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes.  ``tokenizer``
    is the byte scheme so that any vocabulary size passes
    ``get_tokenizer``.  Mamba-1 is a head a channel (``ssm_head_dim``
    1)."""
    import dataclasses

    from distributed_llm_tpu.config import ModelConfig
    lacks = sorted({"attn_window", "ssm_dt_rank"}
                   - {f.name for f in dataclasses.fields(ModelConfig)})
    if lacks:
        raise ValueError(f"{preset}: this program's ModelConfig has no "
                         f"{lacks}: it does not serve the shared-K/V "
                         f"family")
    for key, want in EXPECTED.items():
        if model.get(key, want) != want:
            raise ValueError(f"{preset}: {key} = {model[key]!r}; the "
                             f"family's block is written for {want!r}")
    return ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        layer_pattern=_pattern(preset, model),
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        rotary=False,
        ffn_size=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["layer_norm_eps"]),
        dtype=model.get("torch_dtype", "bfloat16"),
        tie_embeddings=True,
        attn_window=model["sliding_window"],
        ssm_heads=_inner(model), ssm_head_dim=1,
        ssm_state=model["mamba_d_state"],
        ssm_conv=model["mamba_d_conv"],
        ssm_dt_rank=model["mamba_dt_rank"],
        ssm_dt_min=float(model["time_step_min"]),
        ssm_dt_max=float(model["time_step_max"]),
        ssm_dt_floor=float(model["time_step_floor"]))


def rehearsal_model(model: Dict[str, Any], sizes: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The published keys with the configuration's tiny CPU ``sizes`` in
    place; nothing of this family follows from another size."""
    return {**model, **sizes}


# -- parameters, a layer of each kind -----------------------------------------

def mlp_params(model: Dict[str, Any]) -> int:
    """Every layer's second sublayer: ``W1`` to gate and up side by side,
    ``W2`` down; no bias."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def ssm_mixer_params(model: Dict[str, Any]) -> int:
    """One ``M`` mixer: in and out projections, the depthwise conv with
    its bias, the projection to [time step | B | C], the time step's
    up-projection with its bias, A_log a channel and state, D a channel."""
    h, di = model["hidden_size"], _inner(model)
    n, r = model["mamba_d_state"], model["mamba_dt_rank"]
    return (h * 2 * di + di * model["mamba_d_conv"] + di
            + di * (r + 2 * n) + r * di + di + di * n + di + di * h)


def _lambda_and_subnorm(model) -> int:
    return 4 * _head_dim(model) + 2 * _head_dim(model)


def attention_mixer_params(model: Dict[str, Any]) -> int:
    """One ``W`` or ``F`` mixer: q|k|v and o with their biases, the four
    lambda vectors and the pair norm's gain."""
    h, d = model["hidden_size"], _head_dim(model)
    qkv = (model["num_attention_heads"]
           + 2 * model["num_key_value_heads"]) * d
    return (h * qkv + qkv + model["num_attention_heads"] * d * h + h
            + _lambda_and_subnorm(model))


def cross_mixer_params(model: Dict[str, Any]) -> int:
    """One ``X`` mixer: q and o with their biases; no K/V weights."""
    h = model["hidden_size"]
    nq = model["num_attention_heads"] * _head_dim(model)
    return h * nq + nq + nq * h + h + _lambda_and_subnorm(model)


def memory_unit_params(model: Dict[str, Any]) -> int:
    """One ``G`` mixer: in to the memory's width, and out."""
    return 2 * model["hidden_size"] * _inner(model)


def norm_params(model: Dict[str, Any]) -> int:
    """Two LayerNorms a layer and the final one, gain and bias each."""
    return (2 * model["num_hidden_layers"] + 1) * 2 * model["hidden_size"]


def embed_params(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["hidden_size"]


MIXER_PARAMS = {"M": ssm_mixer_params, "W": attention_mixer_params,
                "F": attention_mixer_params, "X": cross_mixer_params,
                "G": memory_unit_params}


def _count(model, kinds: str) -> int:
    pattern = _pattern(model.get("name", "model"), model)
    return sum(pattern.count(kind) for kind in kinds)


def mixer_params(model: Dict[str, Any], kinds: str = KINDS) -> int:
    return sum(_count(model, kind) * MIXER_PARAMS[kind](model)
               for kind in kinds)


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter, the tied table once."""
    return (model["num_hidden_layers"] * mlp_params(model)
            + mixer_params(model) + norm_params(model) + embed_params(model))


def _one_chip(tp: int) -> None:
    if tp != 1:
        raise ValueError("the shared-K/V family is served whole on one "
                         "chip (tp 1): its rows and rings have no shards")


def _itemsize(model) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes the chip HOLDS: every parameter in the served dtype
    (A_log, D, the time step's bias and the lambdas rest in float32: 0.9
    MB more, not counted)."""
    _one_chip(tp)
    return param_count(model) * _itemsize(model)


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """K and V of a position in the ONE cached layer (``F``), in the
    served dtype; window layers keep a ring, state-space layers a row,
    ``X`` and ``G`` layers nothing."""
    return (_count(model, "F") * 2 * model["num_key_value_heads"]
            * _head_dim(model) * _itemsize(model))


def kv_readers(model: Dict[str, Any]) -> int:
    """Layers that read the cached layer's K/V: ``F`` and every ``X``."""
    return _count(model, "FX")


def state_bytes_per_slot(model: Dict[str, Any]) -> int:
    """What a sequence keeps for its state-space layers whatever its
    length: a layer the float32 state (inner x state) and the conv's last
    taps - 1 input rows in the served dtype."""
    di = _inner(model)
    return _count(model, "M") * (
        di * model["mamba_d_state"] * 4
        + (model["mamba_d_conv"] - 1) * di * _itemsize(model))


def ring_bytes_per_slot(model: Dict[str, Any]) -> int:
    """What a sequence keeps for its window layers whatever its length:
    a layer K and V of ``sliding_window`` positions."""
    return (_count(model, "W") * 2 * model["sliding_window"]
            * model["num_key_value_heads"] * _head_dim(model)
            * _itemsize(model))


# -- the chunk scan's kernel (ops/ssm_chunk_scan.py), one call ------------------

SCAN_LANES = 128        # channels a grid step of the kernel holds


def ssm_chunk_scan_ops(model: Dict[str, Any], steps: int) -> int:
    """Operations of one call over ``steps`` positions: a position, a
    state and a channel 7 (the decay's product and its exponential, the
    state's multiply-add, what is fed in, the output's multiply-add), and a
    position and a channel 1 (time step times input)."""
    di = _inner(model)
    return steps * di * (7 * model["mamba_d_state"] + 1)


def ssm_chunk_scan_bytes(model: Dict[str, Any], steps: int) -> int:
    """Bytes one call must move, float32: the time step and the input
    read and the output written a position a channel, B and C read spread
    over one lane width (once: every grid step takes the same block), the
    decay matrix and the state read, the state written."""
    di, n = _inner(model), model["mamba_d_state"]
    return 4 * (3 * steps * di + 2 * steps * n * SCAN_LANES + 3 * n * di)


def chunk_loops(model: Dict[str, Any]):
    """``while`` loops one execution of the chunk program runs on the
    device, (a self-only chunk, a full-depth one): the program scans each
    segment of the pattern that repeats (its own ``layer_segments``), and
    a chunk that does not hold its prompt's last token stops after the
    cached layer's K/V write — it never enters the segments behind it."""
    segments = model_config("loops", model).layer_segments
    at = next(i for i, (period, _) in enumerate(segments) if period == "F")
    return (sum(1 for _, reps in segments[:at] if reps > 1),
            sum(1 for _, reps in segments if reps > 1))


def decode_step_parts(model: Dict[str, Any], contexts: Sequence[float]
                      ) -> Dict[str, float]:
    """The least one chip must move for one decode step of a batch whose
    sequences hold ``contexts`` positions, by part: every MLP, every
    mixer's weights and the norms once, the tied table once as the head
    (not as the embedding: one row a token), the one cached layer's K/V
    of every position ONCE A READER (each of ``kv_readers`` layers
    gathers the window anew), every ring read whole, and the recurrent
    state of every sequence READ AND WRITTEN."""
    b = _itemsize(model)
    return {
        "mlps": model["num_hidden_layers"] * mlp_params(model) * b,
        "mixers": (mixer_params(model) + norm_params(model)) * b,
        "head": embed_params(model) * b,
        "shared_kv": (kv_readers(model) * sum(contexts)
                      * kv_bytes_per_token(model)),
        "rings": len(contexts) * ring_bytes_per_slot(model),
        "state": 2 * len(contexts) * state_bytes_per_slot(model),
    }


def decode_step_bytes_per_chip(model: Dict[str, Any],
                               contexts: Sequence[float], tp: int = 1
                               ) -> float:
    """The sum of ``decode_step_parts``."""
    _one_chip(tp)
    return float(sum(decode_step_parts(model, contexts).values()))
