"""What the harness knows of the state-space / attention decoder with a
dense gated MLP in every layer (AI21's Jamba block at ``num_experts`` 1,
``jamba``): every layer a mixer and then an MLP, RMSNorm before each, a
tied head, no positional term — the mixer attention where ``l %
attn_layer_period == attn_layer_offset``, else Mamba-1 with an RMSNorm on
each of delta, B and C.

Two things, both from the configuration's keys alone: the program's
``ModelConfig`` (a PATTERN of the program's hybrid row family, ``M-`` and
``*-`` a layer: ``models/hybrid_ssm.py``) with this family's checks and
its rule for rehearsal sizes, and the counts the benchmark's shares are
made of — the bytes of a decode step, the chunk scan's kernel's
operations and bytes, the matrix products of one chunk program
(``tests/test_costs_ssm_attention_mlp.py`` holds them to hand-worked
sizes).  The plain forward pass is
``reference/ssm_attention_mlp_decoder.py``, which takes nothing from here.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

from costs import BYTES

# What the program's block assumes of the published keys; any other value
# is a different architecture, refused by name.
EXPECTED = {"model_type": "jamba", "hidden_act": "silu",
            "tie_word_embeddings": True, "num_experts": 1,
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "sliding_window": None}


def mixers(model: Dict[str, Any]) -> str:
    """The mixer of every layer: ``*`` attention, ``M`` Mamba-1."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return "".join("*" if l % period == offset else "M"
                   for l in range(model["num_hidden_layers"]))


def _inner(model) -> int:
    return model["mamba_expand"] * model["hidden_size"]


def _head_dim(model) -> int:
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


def model_config(preset: str, model: Dict[str, Any]):
    """The program's ModelConfig at the published sizes: the hybrid row
    family's pattern of SUBLAYERS, ``M-`` or ``*-`` a layer.
    ``tokenizer`` is the byte scheme so that any vocabulary size passes
    ``get_tokenizer``.  Mamba-1 is a head a channel (``ssm_head_dim``
    1)."""
    from distributed_llm_tpu.config import ModelConfig
    from distributed_llm_tpu.models import hybrid_ssm
    if "-" not in hybrid_ssm.KINDS or not hasattr(hybrid_ssm, "mamba1"):
        raise ValueError(
            f"{preset}: this program's hybrid family (kinds "
            f"{hybrid_ssm.KINDS!r}) has no Mamba-1 rows beside paged "
            f"attention layers and no dense-MLP sublayer: it does not "
            f"serve this family")
    for key, want in EXPECTED.items():
        if model.get(key, want) != want:
            raise ValueError(f"{preset}: {key} = {model[key]!r}; the "
                             f"family's block is written for {want!r}")
    pattern = "".join(kind + "-" for kind in mixers(model))
    return ModelConfig(
        name=preset, tokenizer="byte",
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=len(pattern), layer_pattern=pattern,
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        attn_head_dim=_head_dim(model), rotary=False,
        ffn_size=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]),
        dtype=model.get("torch_dtype", "bfloat16"),
        tie_embeddings=True,
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
    """Every layer's second sublayer: gate, up and down; no bias."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def ssm_matrix_params(model: Dict[str, Any]) -> int:
    """One Mamba-1 mixer's four matrices: in, to [delta | B | C], the time
    step's up-projection, out."""
    h, di = model["hidden_size"], _inner(model)
    n, r = model["mamba_d_state"], model["mamba_dt_rank"]
    return h * 2 * di + di * (r + 2 * n) + r * di + di * h


def ssm_mixer_params(model: Dict[str, Any]) -> int:
    """One Mamba-1 mixer: its matrices, the depthwise conv with its bias,
    the three inner norms' gains, the time step's bias, A_log a channel
    and state, D a channel."""
    di = _inner(model)
    n, r = model["mamba_d_state"], model["mamba_dt_rank"]
    return (ssm_matrix_params(model) + di * model["mamba_d_conv"] + di
            + (r + 2 * n) + di + di * n + di)


def attention_mixer_params(model: Dict[str, Any]) -> int:
    """One attention mixer: q and o over all query heads, k and v over
    the K/V heads; no bias."""
    h, d = model["hidden_size"], _head_dim(model)
    return (2 * h * model["num_attention_heads"] * d
            + 2 * h * model["num_key_value_heads"] * d)


def norm_params(model: Dict[str, Any]) -> int:
    """Two pre-norm gains a layer and the final one."""
    return (2 * model["num_hidden_layers"] + 1) * model["hidden_size"]


def embed_params(model: Dict[str, Any]) -> int:
    return model["vocab_size"] * model["hidden_size"]


def _layers(model):
    """(Mamba layers, attention layers)."""
    kinds = mixers(model)
    return kinds.count("M"), kinds.count("*")


def mixer_params(model: Dict[str, Any]) -> int:
    m, a = _layers(model)
    return m * ssm_mixer_params(model) + a * attention_mixer_params(model)


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter, the tied table once."""
    return (model["num_hidden_layers"] * mlp_params(model)
            + mixer_params(model) + norm_params(model) + embed_params(model))


def _one_chip(tp: int) -> None:
    if tp != 1:
        raise ValueError("the family is served whole on one chip (tp 1): "
                         "its recurrent rows have no shards")


def _itemsize(model) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def weight_bytes_per_chip(model: Dict[str, Any], tp: int = 1) -> int:
    """Weight bytes the chip HOLDS: every parameter in the served dtype
    (A_log, D and the time step's bias rest in float32: 2.4 MB more, not
    counted)."""
    _one_chip(tp)
    return param_count(model) * _itemsize(model)


def kv_bytes_per_token(model: Dict[str, Any]) -> int:
    """K and V of a position over the ATTENTION layers, in the served
    dtype; the Mamba layers keep nothing by position."""
    return (_layers(model)[1] * 2 * model["num_key_value_heads"]
            * _head_dim(model) * _itemsize(model))


def state_bytes_per_slot(model: Dict[str, Any]) -> int:
    """What a sequence keeps for its Mamba layers whatever its length: a
    layer the float32 state (inner x state) and the conv's last taps - 1
    input rows in the served dtype."""
    di = _inner(model)
    return _layers(model)[0] * (
        di * model["mamba_d_state"] * 4
        + (model["mamba_d_conv"] - 1) * di * _itemsize(model))


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


# -- one chunk program ---------------------------------------------------------

def chunk_loops(model: Dict[str, Any]) -> int:
    """``while`` loops one execution of the chunk program runs on the
    device: the program scans the pattern's period, one loop whatever
    the chunk (the scan kernel's loop over positions is inside the
    kernel, a custom call)."""
    segments = model_config("loops", model).layer_segments
    return sum(1 for _, reps in segments if reps > 1)


def chunk_flops_per_chip(model: Dict[str, Any], steps: int, window: int
                         ) -> int:
    """Operations of the MATRIX PRODUCTS of one chunk program over
    ``steps`` positions that attends a window rung of ``window``
    positions (a multiply-add two): every Mamba mixer's four matrices,
    every attention mixer's four and its scores and values over the
    WHOLE rung (masked positions are computed), every MLP's three, and
    the head for the ONE position whose logits the program samples.  The
    scan kernel's elementwise recurrence, the conv and the norms are not
    matrix products and are not counted: a share of the chip's
    matrix-product peak."""
    m, a = _layers(model)
    nq_d = model["num_attention_heads"] * _head_dim(model)
    per_position = (m * ssm_matrix_params(model)
                    + a * attention_mixer_params(model)
                    + model["num_hidden_layers"] * mlp_params(model))
    return (2 * steps * per_position
            + a * 2 * 2 * steps * window * nq_d
            + 2 * embed_params(model))


# -- one decode step -------------------------------------------------------------

def decode_step_parts(model: Dict[str, Any], contexts: Sequence[float]
                      ) -> Dict[str, float]:
    """The least one chip must move for one decode step of a batch whose
    sequences hold ``contexts`` positions, by part: every MLP, every
    mixer's weights and the norms once, the tied table once as the head
    (not as the embedding: one row a token), the attention layers' K/V of
    every position, and the recurrent state of every sequence READ AND
    WRITTEN."""
    b = _itemsize(model)
    return {
        "mlps": model["num_hidden_layers"] * mlp_params(model) * b,
        "mixers": (mixer_params(model) + norm_params(model)) * b,
        "head": embed_params(model) * b,
        "kv": sum(contexts) * kv_bytes_per_token(model),
        "state": 2 * len(contexts) * state_bytes_per_slot(model),
    }


def decode_step_bytes_per_chip(model: Dict[str, Any],
                               contexts: Sequence[float], tp: int = 1
                               ) -> float:
    """The sum of ``decode_step_parts``."""
    _one_chip(tp)
    return float(sum(decode_step_parts(model, contexts).values()))
