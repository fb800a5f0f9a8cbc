"""Roofline accounting: model FLOPs and HBM traffic per serving phase.

The reference could never answer "is it actually fast?" — Ollama hid the
arithmetic (src/devices/nano_api.py:76 just forwards a JSON blob), so its
benchmarks report wall-clock only.  Here every engine phase also accounts
the work the hardware did — matmul FLOPs and HBM bytes, derived from the
model config and the *computed* shapes (padded buckets, masked cache
spans), not the logical token counts — so a reader of GET /stats can
set it against the chip's peaks (the benchmark keeps those, with its own
byte counts: benchmark/peaks.json) and place each phase on the
roofline: prefill is compute-bound (judge by MFU), decode is
bandwidth-bound (judge by HBM utilization).

Conventions (How-to-Scale-Your-Model accounting):
- a matmul of a token through P params is 2·P FLOPs;
- attention scores+values for one query over a span of s keys is 4·h·s
  FLOPs per layer (2 for QKᵀ, 2 for A·V, h = hidden width already
  aggregated over heads);
- masked positions COUNT: the XLA/Pallas decode kernels compute the full
  allocated cache span and mask, so that is the work the MXU executed;
- decode HBM traffic per step = one full weight-set read (shared by the
  whole batch) + each sequence's KV-cache span read.
"""

from __future__ import annotations

from typing import Dict, Optional

def _attention_params(cfg) -> int:
    """One layer's attention matrices."""
    h = cfg.hidden_size
    if cfg.latent:
        nh = cfg.num_heads
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        return (h * cfg.q_lora_rank + cfg.q_lora_rank * nh * (dn + dr)
                + h * cfg.cache_row_width + cfg.kv_lora_rank * nh * (dn + dv)
                + nh * dv * h)
    kv = cfg.num_kv_heads * cfg.head_dim
    return h * h + 2 * h * kv + h * h


def _mamba1_params(cfg) -> int:
    """One Mamba-1 mixer's four matrices (``hybrid_ssm.mamba1``, both row
    families'): in, to [delta | B | C], the time step's, out."""
    h, di = cfg.hidden_size, cfg.ssm_inner
    return (h * 2 * di + di * (cfg.ssm_dt_rank + 2 * cfg.ssm_state)
            + cfg.ssm_dt_rank * di + di * h)


def _hybrid_layer_params(cfg):
    """Matrix parameters of one layer of each kind of the hybrid family
    (models/hybrid_ssm.py): (a row kind's — state-space, or "K" linear
    attention's four projections, two low-rank pairs, beta's and the
    three convs' taps —, attention — a "C" layer's with its two
    convolutions' taps, an "L" layer's latent projections —, an expert
    layer without its routed experts, one routed expert)."""
    h, d = cfg.hidden_size, cfg.head_dim
    di = cfg.ssm_inner
    if cfg.layers_of("K"):
        ssm = (4 * h * di + 2 * cfg.ssm_head_dim * (h + di)
               + h * cfg.ssm_heads + cfg.ssm_conv * 3 * di)
    elif cfg.ssm_dt_rank:
        ssm = _mamba1_params(cfg)
    else:
        ssm = h * (di + cfg.ssm_conv_width + cfg.ssm_heads) + di * h
    if cfg.layers_of("L"):
        nh = cfg.num_heads
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        attn = (h * nh * (dn + dr) + h * cfg.cache_row_width
                + cfg.kv_lora_rank * nh * (dn + dv) + nh * dv * h)
    else:
        attn = 2 * h * cfg.num_heads * d + 2 * h * cfg.num_kv_heads * d
    if cfg.layers_of("C"):
        heads = cfg.num_heads + cfg.num_kv_heads
        attn += 2 * heads * d + 2 * heads * d * d
    rh = cfg.router_hidden
    router = (h * rh + 2 * rh * rh + rh * cfg.num_experts if rh
              else h * cfg.num_experts)
    gated = 2 if cfg.expert_act == "relu2" else 3
    return (ssm, attn, router + gated * h * cfg.shared_ffn_size,
            gated * h * cfg.moe_ffn_size)


def _hybrid_params(cfg, experts: float) -> float:
    """All layers' matrices with ``experts`` routed experts a layer."""
    ssm, attn, fixed, expert = _hybrid_layer_params(cfg)
    return ((cfg.layers_of("M") + cfg.layers_of("K")) * ssm
            + sum(cfg.layers_of(kind) for kind in "*CL") * attn
            + cfg.layers_of("E") * (fixed + experts * expert)
            + cfg.layers_of("-") * 3 * cfg.hidden_size * cfg.ffn_size)


def _shared_kv_params(cfg) -> int:
    """All layers' matrices of the shared-K/V family
    (models/shared_kv_hybrid.py): Mamba-1's four, the window and full
    layers' q|k|v and o, the cross layers' q and o, the memory units' two,
    and a gated MLP every layer."""
    h, di = cfg.hidden_size, cfg.ssm_inner
    nq, kv = cfg.num_heads * cfg.head_dim, cfg.cache_row_width
    return (cfg.layers_of("M") * _mamba1_params(cfg)
            + (cfg.layers_of("W") + 1) * (h * (nq + 2 * kv) + nq * h)
            + cfg.layers_of("X") * 2 * h * nq
            + cfg.layers_of("G") * 2 * h * di
            + cfg.num_layers * 3 * h * cfg.ffn_size)


def kv_readers(cfg) -> int:
    """Layers that read one cached layer's K/V: itself, or the shared-K/V
    family's full layer and every cross layer after it."""
    return 1 + cfg.layers_of("X") if cfg.shared_kv else 1


def ring_row_bytes(cfg) -> int:
    """What one sequence of the shared-K/V family keeps for its window
    layers whatever its length: K and V of the window's positions."""
    return (cfg.layers_of("W") * 2 * cfg.attn_window * cfg.cache_row_width
            * (4 if cfg.dtype == "float32" else 2))


def _layer_counts(cfg):
    """(dense layers, expert layers)."""
    if cfg.num_experts <= 1:
        return cfg.num_layers, 0
    return cfg.dense_lead_layers, cfg.num_layers - cfg.dense_lead_layers


def active_matmul_params(cfg) -> int:
    """Matmul params touched per token: attention + the FFN it goes
    through — a dense one, or ``cfg.experts_per_token`` routed experts
    and ``cfg.shared_experts`` shared ones of the expert width, with the
    router — + the LM head.  Embedding lookup is a gather, not a
    matmul."""
    h = cfg.hidden_size
    if cfg.family == "shared_kv":
        return _shared_kv_params(cfg) + cfg.vocab_size * h
    if cfg.family == "hybrid":
        # Of a token's ``experts_per_token`` choices, the share this
        # program holds computes its fraction (uniform routing).
        held = cfg.experts_per_token * cfg.experts_held / cfg.num_experts
        return int(_hybrid_params(cfg, held)) + cfg.vocab_size * h
    dense, moe = _layer_counts(cfg)
    expert = 3 * h * (cfg.moe_ffn_size or cfg.ffn_size)
    routed = (cfg.experts_per_token + cfg.shared_experts) * expert
    if cfg.latent:
        routed += h * cfg.num_experts           # the float32 router
    return (cfg.num_layers * _attention_params(cfg)
            + dense * 3 * h * cfg.ffn_size + moe * routed
            + cfg.vocab_size * h)


def weight_bytes(cfg, quantize: str = "none") -> int:
    """Resident weight bytes streamed by one decode step.  For MoE this is
    the FULL set of experts held: the dense-dispatch einsum reads every
    expert's weights regardless of routing (models/moe.py); the latent
    family's grouped product reads only the experts a step's tokens chose
    (models/latent_moe.py), so for it this is an upper bound."""
    h = cfg.hidden_size
    per_param = 1 if quantize == "int8" else 2
    if cfg.family == "shared_kv":
        # The tied table once; gains and biases of two norms a layer.
        return (_shared_kv_params(cfg) * per_param
                + (cfg.vocab_size * h + (4 * cfg.num_layers + 2) * h) * 2)
    if cfg.family == "hybrid":
        # Every held expert (an upper bound, as for the latent family).
        tables = 1 if cfg.tie_embeddings else 2
        return (int(_hybrid_params(cfg, cfg.experts_held)) * per_param
                + (tables * cfg.vocab_size * h
                   + (cfg.num_layers + 1) * h) * 2)
    dense, moe = _layer_counts(cfg)
    expert = 3 * h * (cfg.moe_ffn_size or cfg.ffn_size)
    held = cfg.num_experts + cfg.shared_experts
    body = (cfg.num_layers * _attention_params(cfg)
            + dense * 3 * h * cfg.ffn_size + moe * held * expert) * per_param
    # Embedding/head + norms stay bf16 even under int8 weight-only quant.
    heads = 1 if cfg.tie_embeddings else 2
    return body + (heads * cfg.vocab_size * h
                   + (2 * cfg.num_layers + 1) * h) * 2


def kv_bytes_per_pos(cfg, kv_quantize: str = "none") -> int:
    """Cached bytes per position: K+V rows in bf16, or int8 + f32 per-row
    scales (engine/paged_kv.py); the latent family's one row a layer."""
    if cfg.latent:
        return cfg.num_layers * cfg.cache_row_width * 2
    if cfg.layers_of("L"):
        return cfg.kv_layers * cfg.cache_row_width * 2
    rows = 2 * cfg.kv_layers * cfg.num_kv_heads
    if kv_quantize == "int8":
        return rows * (cfg.head_dim + 4)
    return rows * cfg.head_dim * 2


def _attention_width_layers(cfg):
    """(width summed over query heads, layers) of the layers that attend
    over positions: every layer, or the hybrid family's attention ones
    (the shared-K/V family's: the readers of its one cached layer; its
    window layers' fixed span is not counted).  A pattern with "L": the
    mean of a head's score width (nope + rope) and its value width, as a
    chunk up-projects them (64 x 160 where hidden / heads would say 64 x
    64); a decode step's absorbed form does more a position, against the
    latent row itself."""
    if cfg.family == "shared_kv":
        return cfg.num_heads * cfg.head_dim, kv_readers(cfg)
    if cfg.layers_of("L"):
        width = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim
        return cfg.num_heads * width // 2, cfg.kv_layers
    if cfg.family == "hybrid":
        return cfg.num_heads * cfg.head_dim, cfg.kv_layers
    return cfg.hidden_size, cfg.num_layers


def state_row_bytes(cfg) -> int:
    """What one sequence of the hybrid family keeps beside its K/V: the
    float32 state and the conv tail of every state-space layer (a "K"
    layer: a matrix of ``ssm_head_dim`` squared a head and three convs'
    tails), or the tail row of every "C" layer."""
    itemsize = 4 if cfg.dtype == "float32" else 2
    state = cfg.ssm_head_dim if cfg.layers_of("K") else cfg.ssm_state
    return ((cfg.layers_of("M") + cfg.layers_of("K")) * (
        cfg.ssm_inner * state * 4
        + (cfg.ssm_conv - 1) * cfg.ssm_conv_width * itemsize)
        + cfg.layers_of("C") * cfg.cca_tail_width * itemsize)


def prefill_work(cfg, end: int, start: int = 0,
                 wbytes: Optional[int] = None) -> Dict[str, float]:
    """Work for prefilling positions [start, end) of one sequence (end is
    the PADDED/computed span — bucket or chunk stride, not the logical
    prompt length).  Causal attention: position p attends to p+1 keys."""
    pm = active_matmul_params(cfg)
    n = max(0, end - start)
    h, l = _attention_width_layers(cfg)
    flops = 2.0 * pm * n + 2.0 * h * l * float(end**2 - start**2)
    if wbytes is None:
        wbytes = weight_bytes(cfg)
    # One weight-set read per chunk (approximation: prefill is
    # compute-bound, the weight term only anchors the roofline position),
    # plus the KV written for the new span.
    hbm = float(wbytes) + kv_bytes_per_pos(cfg) * n
    return {"flops": flops, "hbm_bytes": hbm, "tokens": n}


def decode_work(cfg, steps: int, ctx: int, batch: int = 1,
                wbytes: Optional[int] = None,
                kv_quantize: str = "none",
                kv_ctx: Optional[float] = None,
                kv_batch: Optional[int] = None) -> Dict[str, float]:
    """Work for ``steps`` sequential decode steps of a ``batch`` of
    sequences whose kernels each span ``ctx`` cached positions (the
    ALLOCATED span the full-span XLA kernels compute over, masked or not).

    ``kv_ctx`` overrides the span per sequence when the ACTIVE kernel
    prunes past the causal frontier: the streamed rows kernel
    (ops/rows_attention.py) reads only a slot's ceil((pos+1)/bs) live
    blocks, not the allocated span, and the batched engine passes that
    mean so hbm_util reflects the blocks the kernel actually moved.  ``kv_batch`` overrides how many
    DISTINCT cache streams one step reads: a chunked verify of γ+1 queries
    reads its shared cache once, not γ+1 times (engine/speculative.py)."""
    pm = active_matmul_params(cfg)
    h, l = _attention_width_layers(cfg)
    span = float(ctx) if kv_ctx is None else min(float(kv_ctx), float(ctx))
    kvb = batch if kv_batch is None else kv_batch
    flops = float(steps) * batch * (2.0 * pm + 4.0 * h * l * span)
    if wbytes is None:
        wbytes = weight_bytes(cfg)
    hbm = float(steps) * (wbytes + kvb * kv_readers(cfg)
                          * kv_bytes_per_pos(cfg, kv_quantize) * span)
    if cfg.hybrid:
        # The recurrent rows: read and written whole, every step; the
        # rings read whole.
        hbm += float(steps) * batch * 2 * state_row_bytes(cfg)
    if cfg.shared_kv:
        hbm += float(steps) * batch * ring_row_bytes(cfg)
    return {"flops": flops, "hbm_bytes": hbm, "tokens": steps * batch}
