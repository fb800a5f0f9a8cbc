"""The state-space / attention / routed-expert hybrid decoder family.

A non-empty ``ModelConfig.layer_pattern`` selects it: one character a
layer, and EACH layer is ONE pre-norm mixer, ``x <- x + mixer(RMSNorm(x))``,
the residual in the model's dtype.  This module also holds what BOTH row
families run (models/shared_kv_hybrid.py imports it): the row machinery
(``claim_row``, ``rows_of``, ``chunk_ctx``, ``decode_ctx``) and the ONE
copy of the Mamba-1 mixer (``mamba1`` and what it calls).

- ``M``, a state-space mixer: **Mamba-1** where ``cfg.ssm_dt_rank > 0``
  (``mamba1``, below: a decay a channel AND state, the chunk stepped in
  order by ``ops/ssm_chunk_scan.py``; in THIS family with an RMSNorm and
  a gain on each of ``delta``, ``B`` and ``C`` before the time-step
  projection, Jamba's block), else
  **Mamba-2**: ``[z | xBC | dt] = x W_in``; a causal depthwise conv
  of ``ssm_conv`` taps (with bias) and silu over ``xBC``; per head ``h`` a
  state ``S_h [head_dim, state]``, ``S_t = exp(dt_t A_h) S_{t-1} + dt_t
  x_t[h] (x) B_t[group of h]``, ``y_t[h] = S_t C_t[group of h] + D_h
  x_t[h]``, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; then
  ``y * silu(z)``, an RMSNorm over each group's channels, ``W_out``.  A
  sequence keeps ``S`` (float32) and the conv's last ``ssm_conv - 1``
  input rows a layer: constant in the context length, a ROW of
  ``pool["s"]`` / ``pool["t"]`` and never a block of the paged pool.
- ``*``, **attention**: grouped-query heads of ``cfg.head_dim``, no rotary
  embedding (``cfg.rotary`` False: position comes from the state-space
  layers), over the K/V blocks of the paged pool — which holds the
  attention layers ONLY.
- ``E``, **experts**: a float32 sigmoid router over ``num_experts``
  outputs, the top ``experts_per_token`` of score + bias chosen (the bias
  moves the choice only), weighed by their normalised scores times
  ``router_scale`` — ``latent_moe.route``, the same form.  This program
  HOLDS experts ``experts_first`` .. ``+ experts_count`` and computes their
  part of the sum; an assignment to an absent expert adds nothing here
  (its rank of the expert-parallel pair adds it), in the plain reference
  alike.  Experts are non-gated, ``W_2 relu(W_1 x)^2``; one shared expert
  of ``shared_ffn_size`` adds for every token.
  Where ``cfg.router_hidden`` the router is an MLP over a state it
  CARRIES through the depth instead (``route_mlp``: a second carry of the
  layer loop beside the residual), softmax scores, the chosen weighed by
  their probabilities as they are; where ``cfg.expert_act`` is ``swiglu``
  the experts are gated, three matrices.
- ``-``, a **dense gated MLP** of ``ffn_size``, ``W_down(silu(W_gate x) *
  (W_up x))`` (``transformer._swiglu``, the dense family's): a layer that
  is a mixer and then an MLP is two characters, ``M-`` or ``*-``.
- ``C``, **compressed convolutional attention** (``_cca``): queries and
  keys are projected once into the heads' own widths, ``u = [q~ | k~]``,
  and everything after lives there — two causal convolutions of two taps
  in turn over ``u`` (depthwise, then grouped by head), the mean of each
  query latent and its K/V group's key latent added back, both
  normalised a head to ``sqrt(head_dim)`` (the keys times a learned
  temperature a K/V head), rotary on the first ``qk_rope_head_dim``
  numbers of a head; the first half of the K/V heads take their values
  from this token and the second half from the token before.  ``k`` (as
  it is attended) and ``v`` go to the paged pool like ``*``'s; what the
  NEXT token needs of this one — the two convolutions' inputs and the
  shifted value — is a ROW of ``pool["t"]`` a slot, as a state-space
  layer's conv tail is, with no state ``pool["s"]`` at all.
- ``K``, **delta-rule linear attention with a decay a channel** (Kimi
  Delta Attention, ``_kda``): ``ssm_heads`` heads whose keys and values
  are ``ssm_head_dim`` wide.  Queries, keys and values each go through a
  causal depthwise conv of ``ssm_conv`` taps (no bias) and silu; queries
  and keys are normalised to unit length a head (the queries then times
  ``d^-1/2``).  A head keeps a MATRIX ``S [d_k, d_v]``, float32: ``S' =
  Diag(exp g_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``,
  ``o_t = S_t^T q_t``, with ``g_t = -exp(A_log[h]) softplus(W_fb W_fa x +
  dt_bias)`` a log-decay a CHANNEL of the keys and ``beta_t =
  sigmoid(w_b[h] . x)``; then an RMSNorm a head with one gain, times
  ``sigmoid(W_gb W_ga x)``, ``W_o``.  A sequence keeps ``S`` and the
  three convs' last ``ssm_conv - 1`` inputs (q, k and v side by side) a
  layer: a ROW of ``pool["s"]`` / ``pool["t"]`` like ``M``'s.  The
  chunk's recurrence is in matrix form (``kda_scan``).
- ``L``, **latent attention**, with or without a rotary term, by
  ``cfg.rotary``: ``latent_moe._attend``, the latent family's own, over
  ONE paged row a token of ``kv_lora_rank`` normalised latent numbers and
  ``qk_rope_head_dim`` numbers shared by every head; queries by one
  matrix.  Under ``rotary`` False the shared numbers are written and read
  UNROTATED (position comes from the ``K`` layers beside it); under True
  they and the queries' last ``qk_rope_head_dim`` numbers a head are
  rotated by YaRN's frequencies and the scores scaled by its magnitude
  (``latent_moe.rope_sincos``, ``softmax_scale``: the row at rest is
  ``[RMSNorm(c) | rotated k_r]``).  A pattern with ``L`` pages
  ``pool["c"]`` [``L`` layers, NB, bs, row] where ``*`` and ``C`` page
  ``"k"`` and ``"v"``.

A pattern with no row kind (no ``M``, ``K`` or ``C``: ``"L-" + "LE" *
5``) keeps ``pool["s"]`` and ``pool["t"]`` as arrays of ZERO layers, like
the empty ``"s"`` beside ``C``'s tails: every program carries them and no
mixer reads them; ``pool["owner"]`` still names a row a slot.

A pattern with ``C`` scales the residual's merge in every sublayer, ``x
<- (a_r x + b_r) + (a_o mixer(..) + b_o)``, four vectors a sublayer
(``lp["res"]``); the others add.

The head is the tree's own ``"head"``, or the embedding where
``cfg.tie_embeddings``.

Whose row: ``pool["owner"][r]`` is the FIRST BLOCK of the sequence that
holds recurrent row ``r`` (0, the trash block: free).  The step functions
of engine/paged_kv.py are handed block tables and never a slot, so a
sequence's row is looked up by its table's first block.  A chunk with
``start == 0`` takes the row that names its block, else the first free
one, and zeroes it; a decode step updates the rows whose block leads one
of its tables and leaves every other row — an idle slot's, a slot's still
in prefill — bit-identical.  The engine keeps the vector itself (row =
slot: ``ContinuousBatchingEngine._sync_state_owner``), so on its path the
lookup only ever finds.

ONE body a layer kind serves the chunk program and the decode tick; the
layer loop is a ``scan`` over PERIODS of the pattern with the period's
kinds inline — after the single sublayers that lead the pattern
(``cfg.layer_lead``: a model's dense lead layer, "K-"), which run inline
ahead of it so that the scan stays the program's one layer loop — and
nothing in a body lowers to a loop (the benchmark tells
a decode tick from a prefill program by how deep its ``while``s nest): the
chunk's recurrence is in matrix form — one block, quadratic in the chunk
length, made for chunks of a few hundred tokens.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..config import ModelConfig
from ..ops import attention, pallas_attention, quant, ssm_chunk_scan
from . import latent_moe, transformer
from .latent_moe import (EMBED_STD, HIGHEST, ROUTER_BIAS_STD, WEIGHT_STD,
                         init_normal, init_table)

Params = Dict[str, Any]
KINDS = "M*E-CKL"
# The routed experts' matrices as a tree may hold them: all three where
# the experts are gated, the last two where not.
EXPERT_KEYS = ("we_gate", "we_up", "we_down")
LANES = 128


def expert_dims_stored(cfg: ModelConfig):
    """(hidden, width) of the routed experts' matrices as STORED: each
    rounded up to whole lane-widths (128) with zero rows and columns (a
    zero input row adds nothing, ``relu(0)^2 = 0``, a zero output column
    is cut off: the same numbers), and no further: 2688 x 1920 (21 and 15
    lane-widths) at the published 2688 x 1856.  At a minor width that is
    not a multiple of the chip's 128 lanes the device rests ``W_1`` [..,
    H, F] with H minor while a kernel wants F minor, so every program
    copied every held expert at its entry (3 x 1.28 GB a tick: compile
    for a described v5e, PR 33); and the chip's compiler cannot cut a
    matrix of such a stack out by index, so ``ops.grouped_product.serves``
    asks for whole lane-widths of ``F`` and ``out`` and whole tiles of 16
    sublanes of ``in``: 128 gives both, for up [held, H, F] and down
    [held, F, H] alike.  From PR 33 to PR 54 the rule was multiples of
    256 (2816 x 2048), for XLA's ``ragged_dot``, which tiles by divisors
    of its dimensions (41 GB/s of the touched experts' bytes at 2688 x
    1856, 112 at 2688 x 1920, 305 at 2688 x 2048, 460 at 2816 x 2048, 581
    at 3072 x 2048: my chip runs, PR 33: 96 rows over 26 of 128 groups);
    ``ops/grouped_product.py`` (PR 34) reads whole matrices whatever
    their factors.  Read at 2688 x 1920 (PR 55): the decode tick and
    every chunk program take both stacks as stored (row-major, F minor
    for up, H minor for down), no operation of either makes an array of
    expert matrices and their temporaries are 0.06 and 0.03-0.15 GB
    (compile for a described v5e; tests/test_tpu_compile.py holds it); on
    the chip ``moe.grouped_product`` reads ``pallas_ffn`` for both stages,
    a tick's one call streams its touched matrices at 694 GB/s and a
    chunk's at 717 (696 and 719 at 2816 x 2048: the same rate, a tenth
    fewer bytes: 774 against 861 us and 1612 against 1796 us an FFN;
    ``ragged_dot`` there 112 and 86 GB/s), and the device's peak memory
    fell 13.48 -> 12.54 GB (my chip runs, PR 55)."""
    def up(n):
        return -(-n // LANES) * LANES
    return up(cfg.hidden_size), up(cfg.moe_ffn_size)


def expert_stacks(params: Params):
    """The routed experts' arrays as the tree holds them: those of the
    period's first ``E`` position (every position's are alike)."""
    first = next(lp for lp in params["periods"] if EXPERT_KEYS[-1] in lp)
    return [first[key] for key in EXPERT_KEYS if key in first]


def check(cfg: ModelConfig) -> None:
    """The pattern and the sizes that have to agree with it."""
    bad = sorted(set(cfg.layer_pattern) - set(KINDS))
    if bad or len(cfg.layer_pattern) != cfg.num_layers:
        raise ValueError(
            f"{cfg.name}: layer_pattern {cfg.layer_pattern!r} has to be "
            f"num_layers = {cfg.num_layers} characters of {KINDS!r}")
    if cfg.ssm_dt_rank:
        if cfg.ssm_head_dim != 1:
            raise ValueError(f"{cfg.name}: Mamba-1 (ssm_dt_rank > 0) is a "
                             f"head a channel (ssm_head_dim 1)")
    elif cfg.ssm_heads % cfg.ssm_groups:
        raise ValueError(f"{cfg.name}: ssm_heads {cfg.ssm_heads} is not a "
                         f"multiple of ssm_groups {cfg.ssm_groups}")
    # What each attention kind needs of the positional term: "*" applies
    # none (the state-space layers beside it carry position), "C" rotates
    # part of every head, "L" the shared numbers of its row where the
    # pattern states rotary.
    if "C" in cfg.layer_pattern:
        rot = cfg.qk_rope_head_dim or cfg.head_dim
        if (not cfg.rotary or rot % 2 or rot > cfg.head_dim
                or cfg.num_kv_heads % 2
                or cfg.num_heads % cfg.num_kv_heads):
            raise ValueError(
                f"{cfg.name}: a pattern with 'C' states rotary True over "
                f"an even qk_rope_head_dim <= head_dim {cfg.head_dim} "
                f"(got {rot}), and an even number of K/V heads (half take "
                f"the shifted value) that divides the query heads")
        if set(cfg.layer_pattern) & set("M*"):
            raise ValueError(
                f"{cfg.name}: the pool's K/V layers and its tail rows are "
                f"indexed by the ONE kind that owns them: a pattern with "
                f"'C' has no 'M' and no '*'")
    elif "*" in cfg.layer_pattern and cfg.rotary:
        raise ValueError(f"{cfg.name}: a pattern with '*' states rotary "
                         f"False: that kind applies no rotary embedding")
    # The rows and the paged arrays are indexed by the ONE kind that owns
    # them: "K" rows beside no "M" or "C", "L"'s latent array beside no
    # "*" or "C"; a latent row's widths come with "L" and only with it.
    if (set(cfg.layer_pattern) >= set("KM")
            or set(cfg.layer_pattern) >= set("KC")
            or ("L" in cfg.layer_pattern
                and set(cfg.layer_pattern) & set("*C"))
            or ("L" in cfg.layer_pattern) != (cfg.kv_lora_rank > 0)
            or ("L" in cfg.layer_pattern and cfg.q_lora_rank)):
        raise ValueError(
            f"{cfg.name}: 'K' rows stand beside no 'M' or 'C', 'L' pages "
            f"its latent row (kv_lora_rank > 0, which only a pattern "
            f"with 'L' states; q_lora_rank 0) beside no '*' or 'C'")
    if "E" not in cfg.layer_pattern:
        return
    if not 0 <= cfg.experts_first <= cfg.num_experts - cfg.experts_held:
        raise ValueError(
            f"{cfg.name}: experts {cfg.experts_first}..+{cfg.experts_held} "
            f"are not among the router's {cfg.num_experts}")
    # What each expert form needs: "relu2" two matrices an expert, no
    # gate; "swiglu" three.
    if cfg.expert_act not in ("relu2", "swiglu"):
        raise ValueError(f"{cfg.name}: expert_act {cfg.expert_act!r}; the "
                         f"hybrid family's experts are 'relu2' (up, down) "
                         f"or 'swiglu' (gate, up, down)")


def kind_index(cfg: ModelConfig, kind: str):
    """Per position of the period: how many layers of ``kind`` come before
    it in the period, and how many the period has."""
    period = cfg.layer_period
    return ([period[:j].count(kind) for j in range(len(period))],
            period.count(kind))


# =============================================================================
# Init: the seed is data, never a constant of the program
# =============================================================================

def init_uniform(key, shape, dtype, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


def init_dt_bias(cfg: ModelConfig, key, width: int):
    """The time step's bias, the published init of both Mamba forms: dt
    log-uniform in [dt_min, dt_max], floored, stored as the inverse of
    softplus."""
    dt = jnp.exp(jax.random.uniform(key, (width,), jnp.float32)
                 * (np.log(cfg.ssm_dt_max) - np.log(cfg.ssm_dt_min))
                 + np.log(cfg.ssm_dt_min))
    dt = jnp.maximum(dt, cfg.ssm_dt_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


# What a pattern with "C" draws non-trivially from the seed, so that a
# dropped term moves the logits: the keys' temperature, the router's
# carry, the merge's gains and offsets.  Its embedding is drawn like
# every matrix (the table is the head too).
TAU_MEAN, TAU_STD = 3.0, 0.25
ROUTER_CARRY_MEAN, ROUTER_CARRY_STD = 0.5, 0.1
RES_GAIN_STD, RES_BIAS_STD = 0.1, 0.02


def init_layer(cfg: ModelConfig, key, kind: str) -> Params:
    """One layer from its own key, split 8 ways."""
    dtype = jnp.dtype(cfg.dtype)
    h = cfg.hidden_size
    ks = jax.random.split(key, 8)
    lp = {"ln": jnp.ones((h,), dtype)}
    if kind == "M" and cfg.ssm_dt_rank:
        lp.update(init_mamba1(cfg, ks[:7], norm_key=ks[7]))
    elif kind == "-":
        f = cfg.ffn_size
        lp.update(w_gate=init_normal(ks[0], (h, f), dtype),
                  w_up=init_normal(ks[1], (h, f), dtype),
                  w_down=init_normal(ks[2], (f, h), dtype))
    elif kind == "M":
        nh, di, c, k = (cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width,
                        cfg.ssm_conv)
        # The published init: A uniform in [1, 16]; D = 1.
        lp.update(
            # [z | xBC | dt], then zero columns up to the chip's lanes:
            # at the published 10304 (80.5 x 128) the device rests the
            # matrix transposed and the tick copied it at its entry.
            w_in=jnp.pad(init_normal(ks[0], (h, di + c + nh), dtype),
                         ((0, 0), (0, -(di + c + nh) % LANES))),
            # A depthwise conv's default init: uniform in +-1/sqrt(taps).
            conv_w=init_uniform(ks[1], (k, c), dtype, k ** -0.5),
            conv_b=init_uniform(ks[2], (c,), dtype, k ** -0.5),
            dt_bias=init_dt_bias(cfg, ks[3], nh),
            a_log=jnp.log(jax.random.uniform(ks[4], (nh,), jnp.float32,
                                             1.0, 16.0)),
            d=jnp.ones((nh,), jnp.float32),
            gn=jnp.ones((di,), dtype),
            w_out=init_normal(ks[5], (di, h), dtype))
    elif kind == "*":
        d, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
        lp.update(wq=init_normal(ks[0], (h, nq * d), dtype),
                  wk=init_normal(ks[1], (h, nkv * d), dtype),
                  wv=init_normal(ks[2], (h, nkv * d), dtype),
                  wo=init_normal(ks[3], (nq * d, h), dtype))
    elif kind == "K":
        nh, d, k = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv
        di = nh * d
        k_fa, k_fb, k_ga, k_gb, k_beta, k_conv = jax.random.split(ks[4], 6)
        # The published layer's init (fla's KimiDeltaAttention): A uniform
        # in [1, 16] a head, the time step's bias as both Mamba forms
        # draw it — a channel's decay then spans a token to a thousand;
        # the three convs' taps at the framework's default, no bias.
        lp.update(wq=init_normal(ks[0], (h, di), dtype),
                  wk=init_normal(ks[1], (h, di), dtype),
                  wv=init_normal(ks[2], (h, di), dtype),
                  wo=init_normal(ks[3], (di, h), dtype),
                  # [q | k | v] channels side by side, as the tail rests.
                  conv_w=init_uniform(k_conv, (k, 3 * di), dtype, k ** -0.5),
                  w_fa=init_normal(k_fa, (h, d), dtype),
                  w_fb=init_normal(k_fb, (d, di), dtype),
                  dt_bias=init_dt_bias(cfg, ks[5], di),
                  a_log=jnp.log(jax.random.uniform(ks[6], (nh,), jnp.float32,
                                                   1.0, 16.0)),
                  w_beta=init_normal(k_beta, (h, nh), dtype),
                  w_ga=init_normal(k_ga, (h, d), dtype),
                  w_gb=init_normal(k_gb, (d, di), dtype),
                  gn=jnp.ones((d,), dtype))
    elif kind == "L":
        nh, dc = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        # ``latent_moe._attend``'s names, with ONE query matrix.
        lp.update(wq=init_normal(ks[0], (h, nh * (dn + dr)), dtype),
                  w_kva=init_normal(ks[1], (h, dc + dr), dtype),
                  kv_ln=jnp.ones((dc,), dtype),
                  w_kvb=init_normal(ks[2], (dc, nh * (dn + dv)), dtype),
                  wo=init_normal(ks[3], (nh * dv, h), dtype))
    elif kind == "C":
        d, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
        c = (nq + nkv) * d
        k_a, k_ab, k_b, k_bb = jax.random.split(ks[4], 4)
        # Two taps each: tap 0 meets the token before, tap 1 this one.
        # The framework's default conv init: uniform in +-1/sqrt(fan-in),
        # 2 for the depthwise conv, 2 x head_dim for the one grouped by
        # head.
        lp.update(wq=init_normal(ks[0], (h, nq * d), dtype),
                  wk=init_normal(ks[1], (h, nkv * d), dtype),
                  # [this token's half of the K/V heads | the shifted half]
                  wv=init_normal(ks[2], (h, nkv * d), dtype),
                  wo=init_normal(ks[3], (nq * d, h), dtype),
                  conv0_w=init_uniform(k_a, (2, c), dtype, 2 ** -0.5),
                  conv0_b=init_uniform(k_ab, (c,), dtype, 2 ** -0.5),
                  conv1_w=init_uniform(k_b, (2, nq + nkv, d, d), dtype,
                                       (2 * d) ** -0.5),
                  conv1_b=init_uniform(k_bb, (c,), dtype, (2 * d) ** -0.5),
                  tau=TAU_MEAN + TAU_STD * jax.random.normal(
                      ks[5], (nkv,), jnp.float32))
    else:
        f, e = cfg.moe_ffn_size, cfg.num_experts
        held = slice(cfg.experts_first, cfg.experts_first + cfg.experts_held)
        h_st, f_st = expert_dims_stored(cfg)
        k_gate, k_router = jax.random.split(ks[6])

        def experts(key, shape, stored):
            # A key a ROUTER OUTPUT, the held ones taken: an expert's
            # matrix is the same whichever share holds it.  One expert at
            # a time (the float32 draws beside the result are one's),
            # zero-padded to ``expert_dims_stored``.
            pad = [(0, st - n) for n, st in zip(shape, stored)]
            return jax.lax.map(
                lambda k: jnp.pad(init_normal(k, shape, dtype), pad),
                jax.random.split(key, e)[held])

        lp.update(router_bias=ROUTER_BIAS_STD * jax.random.normal(
                      ks[1], (e,), jnp.float32),
                  we_up=experts(ks[2], (h, f), (h_st, f_st)),
                  we_down=experts(ks[3], (f, h), (f_st, h_st)))
        if cfg.expert_act == "swiglu":
            lp.update(we_gate=experts(k_gate, (h, f), (h_st, f_st)))
        if cfg.router_hidden:
            # The MLP router: the down-projection like any matrix; the
            # MLP's own at unit gain (1/sqrt(fan-in)), so that its scores
            # spread over the experts with the token and the choice-only
            # bias moves a choice by a hair; the carry's gain drawn AWAY
            # from 0 and 1.
            rh = cfg.router_hidden
            k_c, k_1, k_2, k_3 = jax.random.split(k_router, 4)
            lp.update(
                router=init_normal(ks[0], (h, rh), dtype),
                router_carry=ROUTER_CARRY_MEAN + ROUTER_CARRY_STD
                * jax.random.normal(k_c, (rh,), jnp.float32),
                router_ln=jnp.ones((rh,), dtype),
                router_w1=init_normal(k_1, (rh, rh), dtype, rh ** -0.5),
                router_w2=init_normal(k_2, (rh, rh), dtype, rh ** -0.5),
                router_w3=init_normal(k_3, (rh, e), dtype, rh ** -0.5))
        else:
            lp.update(router=init_normal(ks[0], (h, e), dtype))
        if cfg.shared_ffn_size:
            # Of the experts' own form: gated where they are.
            fs = cfg.shared_ffn_size
            lp.update(ws_up=init_normal(ks[4], (h, fs), dtype),
                      ws_down=init_normal(ks[5], (fs, h), dtype))
            if cfg.expert_act == "swiglu":
                lp.update(ws_gate=init_normal(ks[7], (h, fs), dtype))
    if "C" in cfg.layer_pattern:
        # The scaled merge of every sublayer of such a pattern: (a_r, b_r,
        # a_o, b_o), the gains drawn about 1 and the offsets about 0.
        draw = jax.random.normal(ks[7], (4, h), jnp.float32)
        lp.update(res=jnp.array([1.0, 0.0, 1.0, 0.0])[:, None]
                  + jnp.array([RES_GAIN_STD, RES_BIAS_STD] * 2)[:, None]
                  * draw)
    return lp


def init_params(cfg: ModelConfig, seed=0) -> Params:
    """``seed`` may be traced (jit this with the seed as an ARGUMENT: one
    compiled program makes every seed's weights).  ``periods[j]`` holds
    position ``j`` of the period for every period, stacked — what the
    layer loop scans; ``lead[j]`` sublayer ``j`` of ``cfg.layer_lead``,
    one layer each; layer ``l`` draws from key ``l`` of ``num_layers``."""
    check(cfg)
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_head, k_layers = jax.random.split(jax.random.PRNGKey(seed), 3)
    lkeys = jax.random.split(k_layers, cfg.num_layers)
    lead, period = cfg.layer_lead, cfg.layer_period
    lead_keys, lkeys = lkeys[:len(lead)], lkeys[len(lead):]
    params = {
        "embed": init_table(k_embed, cfg.vocab_size, cfg.hidden_size, dtype,
                            WEIGHT_STD if "C" in cfg.layer_pattern
                            else EMBED_STD),
        "final_ln": jnp.ones((cfg.hidden_size,), dtype),
        "periods": [jax.lax.map(lambda k, c=kind: init_layer(cfg, k, c),
                                lkeys[j::len(period)])
                    for j, kind in enumerate(period)],
    }
    if lead:
        params["lead"] = [init_layer(cfg, lead_keys[j], kind)
                          for j, kind in enumerate(lead)]
    if not cfg.tie_embeddings:
        params["head"] = init_table(k_head, cfg.vocab_size, cfg.hidden_size,
                                    dtype)
    return params


# =============================================================================
# Whose recurrent row
# =============================================================================

def claim_row(owner: jax.Array, first_block: jax.Array, fresh: jax.Array):
    """(row, owner) for the sequence whose table starts at
    ``first_block``: the row that names it, else — a ``fresh`` sequence
    only — the first free row, which then names it."""
    named = owner == first_block
    row = jnp.where(jnp.any(named), jnp.argmax(named),
                    jnp.argmax(owner == 0)).astype(jnp.int32)
    return row, jnp.where(fresh, owner.at[row].set(first_block), owner)


def rows_of(owner: jax.Array, first_blocks: jax.Array):
    """For a decode batch whose tables start at ``first_blocks`` [B]:
    (the batch index a row takes its input from [R], whether any does
    [R], the row a batch index reads its output from [B])."""
    named = (owner[:, None] == first_blocks[None, :]) & (owner[:, None] != 0)
    return (jnp.argmax(named, axis=1), jnp.any(named, axis=1),
            jnp.argmax(named, axis=0))


# =============================================================================
# The causal depthwise conv over a row's tail: every kind's that keeps one
# =============================================================================

def conv_step(lp: Params, tail, a, valid):
    """One token a ROW through the conv and silu: a [R, C] the row's
    token, tail [R, K-1, C] the inputs before it, valid [R].  Returns (u
    [R, C] float32, tail); a row that is not ``valid`` keeps its tail
    bit-identical.  The bias where the layer holds one."""
    window = jnp.concatenate([tail, a[:, None]], axis=1)         # [R, K, C]
    u = jnp.sum(window.astype(jnp.float32)
                * lp["conv_w"].astype(jnp.float32), axis=1)
    if "conv_b" in lp:
        u = u + lp["conv_b"].astype(jnp.float32)
    return jax.nn.silu(u), jnp.where(valid[:, None, None], window[:, 1:],
                                     tail)


def conv_chunk(lp: Params, tail, a, n_valid):
    """A CHUNK of one sequence through the same: a [S, C] from ``tail``
    [K-1, C]; the tail that comes back is the last ``K - 1`` VALID rows'
    (positions ``>= n_valid`` are right padding).  Returns (u [S, C]
    float32, tail)."""
    s_c, k = a.shape[0], tail.shape[0] + 1
    seq = jnp.concatenate([tail, a], axis=0)                     # [S+K-1, C]
    w = lp["conv_w"].astype(jnp.float32)
    u = sum(seq[j:j + s_c].astype(jnp.float32) * w[j] for j in range(k))
    if "conv_b" in lp:
        u = u + lp["conv_b"].astype(jnp.float32)
    return (jax.nn.silu(u),
            jax.lax.dynamic_slice_in_dim(seq, n_valid, k - 1, axis=0))


def _row_major(x):
    """``x``, a pool array on its way back into a loop's carry, held to
    the order it rests in (``engine/paged_kv.py``): without it the chip's
    compiler lays the WHOLE array out in the operand order a recurrence's
    products over ONE row prefer and copies it, whole, into the layer
    loop and out (``kimi-linear-48b-a3b``'s chunk program: 2 x 235 MB a
    chunk; compile for a described v5e, PR 57).  With it the row is what
    gets re-laid, if anything is; where the compiler kept the order
    anyway the program is the same, instruction for instruction.  A
    constraint inside the program, not a pinned argument: the program's
    edge keeps the device's default, which the persistent compile cache
    keeps too."""
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def through_rows(pool, li, ctx, scan, step, kind: str):
    """Layer ``li``'s state and tail through a row kind's recurrence: a
    chunk of one sequence takes its row (zeros where the sequence is
    fresh) through ``scan(state, tail, n_valid)``, a decode step takes
    every row through ``step(src, state, tail, valid)`` (``src`` the batch
    index a row reads its token from); both give (y, state, tail).
    Returns (y [B, S, width], the pool with the rows written back).  The
    rows' read and write-back carry the scopes of the recurrence whose
    rows they are (``kind`` "ssm" or "kda": the state under
    ``<kind>_scan`` / ``<kind>_step``, the tail under ``<kind>_conv``), so
    an update the compiler fuses with its write-back has one name.  The
    state array goes back into the carry in the order it rests in
    (``_row_major``), whichever kind the rows are."""
    s_all, t_all = pool["s"], pool["t"]
    of_tail = jax.named_scope(f"{kind}_conv")
    if "row" in ctx:                               # a chunk of one sequence
        row, fresh = ctx["row"], ctx["fresh"]
        of_state = jax.named_scope(f"{kind}_scan")
        with of_state:
            state = jnp.where(fresh, 0.0, s_all[li, row])
        with of_tail:
            tail = jnp.where(fresh, jnp.zeros((), t_all.dtype),
                             t_all[li, row])
        y, state, tail = scan(state, tail, ctx["n_valid"])
        with of_state:
            y, s_all = y[None], _row_major(s_all.at[li, row].set(state))
        with of_tail:
            return y, {**pool, "s": s_all, "t": t_all.at[li, row].set(tail)}
    src, valid, dst = ctx["rows"]                  # a decode step, by rows
    of_state = jax.named_scope(f"{kind}_step")
    with of_state:
        state = s_all[li]
    with of_tail:
        tail = t_all[li]
    y, state, tail = step(src, state, tail, valid)
    with of_state:
        y, s_all = y[dst][:, None], _row_major(s_all.at[li].set(state))
    with of_tail:
        return y, {**pool, "s": s_all, "t": t_all.at[li].set(tail)}


# =============================================================================
# The Mamba-1 mixer: both row families' (models/shared_kv_hybrid.py's "M")
# =============================================================================

INNER_NORM_STD = 0.1


def init_mamba1(cfg: ModelConfig, ks, norm_key=None) -> Params:
    """One Mamba-1 mixer's weights from seven keys (in, conv, conv bias,
    x, time step, the time step's bias, out).  The published init:
    ``init_dt_bias``; A = 1..state a channel; D 1.  ``norm_key``: the mixer
    normalises ``delta``, ``B`` and ``C`` (an RMSNorm each) and holds
    their gains, drawn AWAY from 1 so that a dropped gain moves the
    logits; without it the tree holds none and ``_time_step`` runs none."""
    dtype = jnp.dtype(cfg.dtype)
    h = cfg.hidden_size
    di, n, k, r = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    lp = dict(
        w_in=init_normal(ks[0], (h, 2 * di), dtype),
        conv_w=init_uniform(ks[1], (k, di), dtype, k ** -0.5),
        conv_b=init_uniform(ks[2], (di,), dtype, k ** -0.5),
        w_x=init_normal(ks[3], (di, r + 2 * n), dtype),
        w_dt=init_uniform(ks[4], (r, di), dtype, r ** -0.5),
        dt_bias=init_dt_bias(cfg, ks[5], di),
        # [state, inner], as the state rests.
        a_log=jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, di)),
        d=jnp.ones((di,), jnp.float32),
        w_out=init_normal(ks[6], (di, h), dtype))
    if norm_key is not None:
        for name, key, width in zip(("dt_ln", "b_ln", "c_ln"),
                                    jax.random.split(norm_key, 3),
                                    (r, n, n)):
            lp[name] = (1.0 + INNER_NORM_STD * jax.random.normal(
                key, (width,), jnp.float32)).astype(dtype)
    return lp


def _time_step(cfg: ModelConfig, lp: Params, u):
    """u [..., inner] float32, the conv's output -> (dt [..., inner], B
    and C [..., state]): the two small projections in float32 at the
    highest precision — the time step's error compounds through every
    later position of the state.  Where the layer's weights hold the
    inner norms' gains, ``delta``, ``B`` and ``C`` are RMS-normalised
    between the two projections."""
    r, n = cfg.ssm_dt_rank, cfg.ssm_state
    dbc = jnp.einsum("...c,cr->...r", u, lp["w_x"].astype(jnp.float32),
                     precision=HIGHEST)
    delta, b, c = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    if "dt_ln" in lp:
        with jax.named_scope("ssm_inner_norms"):
            delta, b, c = (_norm_f32(a, lp[key], cfg.norm_eps)
                           for a, key in ((delta, "dt_ln"), (b, "b_ln"),
                                          (c, "c_ln")))
    dt = jnp.einsum("...r,rc->...c", delta, lp["w_dt"].astype(jnp.float32),
                    precision=HIGHEST)
    return jax.nn.softplus(dt + lp["dt_bias"]), b, c


def mamba1_step(cfg: ModelConfig, lp: Params, a, state, tail, valid):
    """The one-step recurrence over ROWS: a [R, inner] the row's token
    (before the conv), state [R, state, inner] float32, tail [R, K-1,
    inner], valid [R].  Returns (m [R, inner] float32, state, tail); a row
    that is not ``valid`` keeps both bit-identical."""
    with jax.named_scope("ssm_conv"):
        u, tail = conv_step(lp, tail, a, valid)
    with jax.named_scope("ssm_step"):
        dt, b, c = _time_step(cfg, lp, u)
        decay = jnp.exp(dt[:, None, :] * -jnp.exp(lp["a_log"]))
        new = decay * state + (dt * u)[:, None, :] * b[:, :, None]
        new = jnp.where(valid[:, None, None], new, state)
        m = jnp.sum(new * c[:, :, None], axis=1) + lp["d"] * u
    return m, new, tail


def scan_unrolled(dt, u, b, c, a_mat, state):
    """``ops.ssm_chunk_scan`` as XLA operations, the loop unrolled when
    the program is traced: what the kernel is held to, and what the CPU's
    tests run at sizes the kernel does not serve.  Never a compiled
    program's (``mamba1_scan`` refuses): the chip's compiler takes
    minutes a program over its bodies."""
    fed = dt * u
    ys = []
    for t in range(dt.shape[0]):
        state = (jnp.exp(dt[t][None, :] * a_mat) * state
                 + fed[t][None, :] * b[t][:, None])
        ys.append(jnp.sum(state * c[t][:, None], axis=0))
    return jnp.stack(ys), state


def mamba1_scan(cfg: ModelConfig, lp: Params, a, state, tail, n_valid):
    """The same recurrence over a CHUNK of one sequence, a position at a
    time in order (``ops.ssm_chunk_scan``: the loop is the kernel's, none
    is lowered): a [S, inner], from ``state`` [state, inner] and ``tail``
    [K-1, inner]; positions ``>= n_valid`` are right padding — their time
    step is 0, so they neither decay nor feed the state, and the tail is
    taken from the last valid rows.  The kernel is handed ``dt``, ``u``,
    ``a_mat`` and the state as they rest here (it reads their tiles in
    place, 1024 channels a register) and ``B`` and ``C`` as the
    projection's two narrow slices (it reads them as scalars): nothing is
    spread or re-laid for it.  Returns (m [S, inner] float32, state,
    tail)."""
    s_c = a.shape[0]
    with jax.named_scope("ssm_conv"):
        u, tail = conv_chunk(lp, tail, a, n_valid)
    with jax.named_scope("ssm_scan"):
        dt, b, c = _time_step(cfg, lp, u)
        dt = jnp.where((jnp.arange(s_c) < n_valid)[:, None], dt, 0.0)
        a_mat = -jnp.exp(lp["a_log"])                        # [N, inner]
        if ssm_chunk_scan.serves(s_c, cfg.ssm_state, cfg.ssm_inner):
            scan = ssm_chunk_scan.ssm_chunk_scan
        elif pallas_attention.kernel_mode() == "interpret":   # CPU tests
            scan = scan_unrolled
        else:
            raise ValueError(
                f"{cfg.name}: ops.ssm_chunk_scan does not serve a chunk "
                f"of {s_c} positions x ssm_state {cfg.ssm_state} x "
                f"ssm_inner {cfg.ssm_inner} (whole lane-widths of "
                f"channels, states and positions in eights, the chunk's "
                f"dt, u and y in VMEM), and nothing else scans a chunk in "
                f"a compiled program")
        y, state = scan(dt, u, b, c, a_mat, state)
        m = y + lp["d"] * u
    return m, state, tail


def mamba1(cfg: ModelConfig, lp: Params, h_in, pool, li, ctx):
    """h_in [B, S, H] -> (mixer output, pool, the scan's output m [B, S,
    inner] float32, before the gate and with the ``D`` term); ``li`` the
    layer's index among the state-space layers.  ``pool["s"]`` [layers,
    R, state, inner] float32 (the channels fill the chip's lanes),
    ``pool["t"]`` [layers, R, K-1, inner]."""
    di = cfg.ssm_inner
    with jax.named_scope("ssm_in_proj"):
        az = quant.matmul(h_in, lp["w_in"])
        a, z = az[..., :di], az[..., di:]
    m, pool = through_rows(
        pool, li, ctx,
        lambda state, tail, n: mamba1_scan(cfg, lp, a[0], state, tail, n),
        lambda src, state, tail, valid: mamba1_step(
            cfg, lp, a[src, 0], state, tail, valid), "ssm")
    out = (m * jax.nn.silu(z.astype(jnp.float32))).astype(h_in.dtype)
    return quant.matmul(out, lp["w_out"]), pool, m


# =============================================================================
# This family's own mixers: Mamba-2, attention, experts, the MLP
# =============================================================================

def _split_in(cfg: ModelConfig, zxbcdt: jax.Array):
    di, c = cfg.ssm_inner, cfg.ssm_conv_width
    return (zxbcdt[..., :di], zxbcdt[..., di:di + c],
            zxbcdt[..., di + c:di + c + cfg.ssm_heads])


def _heads(cfg: ModelConfig, u: jax.Array):
    """Conv output [..., C] float32 -> x [..., G, heads a group, P],
    B and C [..., G, N]."""
    g, n, p = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
    di = cfg.ssm_inner
    lead = u.shape[:-1]
    return (u[..., :di].reshape(*lead, g, cfg.ssm_heads // g, p),
            u[..., di:di + g * n].reshape(*lead, g, n),
            u[..., di + g * n:].reshape(*lead, g, n))


def _by_group(cfg: ModelConfig, a: jax.Array):
    """[..., heads] -> [..., G, heads a group]."""
    return a.reshape(*a.shape[:-1], cfg.ssm_groups,
                     cfg.ssm_heads // cfg.ssm_groups)


def ssm_step(cfg: ModelConfig, lp: Params, xbc, dt, state, tail, valid):
    """The one-step recurrence over ROWS: xbc [R, C] and dt [R, heads] the
    row's token, state [R, heads, P, N] float32, tail [R, K-1, C], valid
    [R].  Returns (y [R, inner] float32, state, tail); a row that is not
    ``valid`` keeps both bit-identical."""
    r = xbc.shape[0]
    g = cfg.ssm_groups
    with jax.named_scope("ssm_conv"):
        u, tail = conv_step(lp, tail, xbc, valid)
    with jax.named_scope("ssm_step"):
        x, b, c = _heads(cfg, u)
        dt = _by_group(cfg, jax.nn.softplus(dt.astype(jnp.float32)
                                            + lp["dt_bias"]))
        decay = jnp.exp(dt * _by_group(cfg, -jnp.exp(lp["a_log"])))
        s = state.reshape(r, g, -1, *state.shape[2:])      # [R, G, k, P, N]
        new = (s * decay[..., None, None]
               + (dt[..., None] * x)[..., None] * b[:, :, None, None, :])
        new = jnp.where(valid[:, None, None, None, None], new, s)
        y = (jnp.sum(new * c[:, :, None, None, :], axis=-1)
             + _by_group(cfg, lp["d"])[..., None] * x)
    return y.reshape(r, -1), new.reshape(state.shape), tail


def ssm_scan(cfg: ModelConfig, lp: Params, xbc, dt, state, tail, n_valid):
    """The same recurrence over a CHUNK of one sequence, in matrix form:
    xbc [S, C], dt [S, heads], from ``state`` [heads, P, N] and ``tail``
    [K-1, C]; positions ``>= n_valid`` are right padding — their time step
    is 0, so they neither decay nor feed the state, and the tail is taken
    from the last valid rows.  Returns (y [S, inner] float32, state,
    tail).  With ``a_t = dt_t A`` and ``cum`` its running sum,

        y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
              + exp(cum_t) C_t . S_0 + D x_t
        S_end = exp(cum_T) S_0 + sum_s exp(cum_T - cum_s) dt_s x_s (x) B_s

    every product a float32 einsum at the highest precision (under 1
    GFLOP a layer a chunk of 256)."""
    s_c = xbc.shape[0]
    g = cfg.ssm_groups
    with jax.named_scope("ssm_conv"):
        u, tail = conv_chunk(lp, tail, xbc, n_valid)
    with jax.named_scope("ssm_scan"):
        x, b, c = _heads(cfg, u)              # [S, G, k, P], [S, G, N] x 2
        live = (jnp.arange(s_c) < n_valid)[:, None]
        dt = jnp.where(live, jax.nn.softplus(dt.astype(jnp.float32)
                                             + lp["dt_bias"]), 0.0)
        a = dt * -jnp.exp(lp["a_log"])                           # [S, heads]
        causal = jnp.tril(jnp.ones((s_c, s_c), bool))
        # Heads lead and the chunk's positions are minor: [.., t, s].
        cum = jnp.einsum("ts,sh->ht", causal.astype(jnp.float32), a,
                         precision=HIGHEST)                      # [heads, S]
        # exp(cum_t - cum_s) for s <= t: never above 1.
        gap = jnp.where(causal, cum[:, :, None] - cum[:, None, :], -jnp.inf)
        cb = jnp.einsum("tgn,sgn->gts", c, b, precision=HIGHEST)
        mix = (jnp.exp(gap).reshape(g, -1, s_c, s_c) * cb[:, None])
        xdt = x * _by_group(cfg, dt)[..., None]               # [S, G, k, P]
        s0 = state.reshape(g, -1, *state.shape[1:])           # [G, k, P, N]
        cum_g = _by_group(cfg, cum.T)                            # [S, G, k]
        y = (jnp.einsum("gkts,sgkp->tgkp", mix, xdt, precision=HIGHEST)
             + jnp.einsum("tgn,gkpn->tgkp", c, s0, precision=HIGHEST)
             * jnp.exp(cum_g)[..., None]
             + _by_group(cfg, lp["d"])[..., None] * x)
        to_end = jnp.exp(cum_g[-1][None] - cum_g)                # [S, G, k]
        new = (jnp.exp(cum_g[-1])[..., None, None] * s0
               + jnp.einsum("sgkp,sgn->gkpn", xdt * to_end[..., None], b,
                            precision=HIGHEST))
    return y.reshape(s_c, -1), new.reshape(state.shape), tail


def _gate_norm(cfg: ModelConfig, lp: Params, y, z):
    """``y * silu(z)``, an RMSNorm over each group's channels, the gain;
    float32 in, the model's dtype out."""
    with jax.named_scope("ssm_gate_norm"):
        y = y * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(*y.shape[:-1], cfg.ssm_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True)
            + cfg.norm_eps)
        return grouped.reshape(y.shape).astype(z.dtype) * lp["gn"]


def _mamba(cfg: ModelConfig, lp: Params, h_in, pool, li, ctx):
    """h_in [B, S, H] -> (mixer output, pool); ``li`` the layer's index
    among the state-space layers."""
    with jax.named_scope("ssm_in_proj"):
        z, xbc, dt = _split_in(cfg, quant.matmul(h_in, lp["w_in"]))
    y, pool = through_rows(
        pool, li, ctx,
        lambda state, tail, n: ssm_scan(cfg, lp, xbc[0], dt[0], state, tail,
                                        n),
        lambda src, state, tail, valid: ssm_step(
            cfg, lp, xbc[src, 0], dt[src, 0], state, tail, valid), "ssm")
    return quant.matmul(_gate_norm(cfg, lp, y, z), lp["w_out"]), pool


def _attend(cfg: ModelConfig, lp: Params, q, k, v, pool, li, ctx):
    """q [B, S, N_q, D], k and v [B, S, N_kv * D] as they are cached ->
    (mixer output, pool): the rows written at ``blk, off`` of K/V layer
    ``li``, the table's window attended, ``W_o``."""
    b, s = q.shape[:2]
    blk, off = ctx["blk"], ctx["off"]
    with jax.named_scope("kv_write"):
        k_p = pool["k"].at[li, blk, off].set(k)
        v_p = pool["v"].at[li, blk, off].set(v)
    with jax.named_scope("attention"):
        if "row" in ctx:
            out = attention.paged_chunk(
                q, k_p, v_p, ctx["table"], ctx["q_pos"], ctx["window"],
                layer=li)
        else:
            out = attention.paged_decode(
                q[:, 0], k_p, v_p, ctx["tables"], ctx["pos"],
                impl=cfg.attention_impl, layer=li)
    out = quant.matmul(out.reshape(b, s, -1), lp["wo"])
    return out, {**pool, "k": k_p, "v": v_p}


def _attention(cfg: ModelConfig, lp: Params, h_in, pool, li, ctx):
    """h_in [B, S, H] -> (mixer output, pool); ``li`` the layer's index
    among the attention layers, which are the K/V pool's layers."""
    b, s, _ = h_in.shape
    q = quant.matmul(h_in, lp["wq"]).reshape(b, s, cfg.num_heads,
                                             cfg.head_dim)
    return _attend(cfg, lp, q, quant.matmul(h_in, lp["wk"]),
                   quant.matmul(h_in, lp["wv"]), pool, li, ctx)


def cca_conv(cfg: ModelConfig, lp: Params, u, v2, tail):
    """The two convolutions and the value shift over ``S`` positions in
    order that follow the position ``tail`` describes: u [N, S, C] the
    latents ``[q~ | k~]``, v2 [N, S, Dv] the values the shifted K/V heads
    will take, tail [N, cca_tail_width] = the position before's ``[u | c
    | v2]`` (zeros before a sequence's first).  Returns (d [N, S, C]
    float32, the shifted values [N, S, Dv], every position's own ``[u | c
    | v2]`` with the tail's in front [N, S + 1, cca_tail_width]: what
    the next call is handed is a row of it).  ``c`` is rounded to the
    model's dtype BEFORE the second convolution reads it, so what the
    tail keeps at rest is what an unbroken pass would have read: a
    sequence cut into chunks and steps anywhere gives the same numbers."""
    n, s, c_w = u.shape
    d = cfg.head_dim
    f32 = jnp.float32
    seq_u = jnp.concatenate([tail[:, None, :c_w], u], axis=1)
    w0 = lp["conv0_w"].astype(f32)
    c = (seq_u[:, :-1].astype(f32) * w0[0] + seq_u[:, 1:].astype(f32) * w0[1]
         + lp["conv0_b"].astype(f32)).astype(u.dtype)
    seq_c = jnp.concatenate([tail[:, None, c_w:2 * c_w], c], axis=1)
    by_head = seq_c.reshape(n, s + 1, c_w // d, d)
    # Float32 operands at the default precision: on the chip one bfloat16
    # pass accumulated in float32, exact for operands that ARE bfloat16.
    by_head, w1 = by_head.astype(f32), lp["conv1_w"].astype(f32)
    out = sum(jnp.einsum("nsgi,gio->nsgo", by_head[:, j:j + s], w1[j])
              for j in range(2))
    out = out.reshape(n, s, c_w) + lp["conv1_b"].astype(f32)
    seq_v = jnp.concatenate([tail[:, None, 2 * c_w:], v2], axis=1)
    return out, seq_v[:, :-1], jnp.concatenate([seq_u, seq_c, seq_v], -1)


def cca_qk(cfg: ModelConfig, lp: Params, u, conv, positions):
    """u [N, S, C] the latents before the convolutions, conv [N, S, C]
    float32 after them, positions [N, S] -> (q [N, S, N_q, D], k [N, S,
    N_kv * D]) as attended and cached: the q-k mean of the latents added
    to the convolutions' output, each head normalised to sqrt(D) (a
    key's times its K/V head's temperature), the first
    ``qk_rope_head_dim`` numbers of every head rotated."""
    n, s, _ = u.shape
    d, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    lat = u.astype(jnp.float32).reshape(n, s, nq + nkv, d)
    conv = conv.reshape(n, s, nq + nkv, d)
    mean_q = 0.5 * (lat[:, :, :nq]
                    + jnp.repeat(lat[:, :, nq:], nq // nkv, axis=2))
    mean_k = jnp.mean(mean_q.reshape(n, s, nkv, nq // nkv, d), axis=3)
    q, k = conv[:, :, :nq] + mean_q, conv[:, :, nq:] + mean_k

    def unit(x):
        norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
        return x * (d ** 0.5 / jnp.maximum(norm, 1e-12))
    q, k = unit(q), unit(k) * lp["tau"][:, None]
    rot = cfg.qk_rope_head_dim or d
    sin, cos = transformer.rope_sincos(positions, rot, cfg.rope_theta)

    def rope(x):
        return jnp.concatenate(
            [transformer.apply_rope(x[..., :rot], sin, cos), x[..., rot:]],
            axis=-1)
    dtype = u.dtype
    return rope(q).astype(dtype), rope(k).astype(dtype).reshape(n, s, -1)


def _cca(cfg: ModelConfig, lp: Params, h_in, pool, li, ctx):
    """h_in [B, S, H] -> (mixer output, pool); ``li`` the layer's index
    among the "C" layers, which are the K/V pool's layers AND the tail
    rows' (``pool["t"]`` [layers, R, 1, cca_tail_width])."""
    half = cfg.num_kv_heads // 2 * cfg.head_dim
    with jax.named_scope("cca_proj"):
        u = jnp.concatenate([quant.matmul(h_in, lp["wq"]),
                             quant.matmul(h_in, lp["wk"])], axis=-1)
        v = quant.matmul(h_in, lp["wv"])
        v1, v2 = v[..., :half], v[..., half:]
    t_all = pool["t"]
    with jax.named_scope("cca_conv"):
        if "row" in ctx:                           # a chunk of one sequence
            row = ctx["row"]
            tail = jnp.where(ctx["fresh"], jnp.zeros((), t_all.dtype),
                             t_all[li, row])                       # [1, W]
            conv, shifted, seq = cca_conv(cfg, lp, u, v2, tail)
            # Row ``n_valid`` of [tail | chunk] is the last valid one's.
            tail = jax.lax.dynamic_slice_in_dim(seq[0], ctx["n_valid"], 1)
            pool = {**pool, "t": t_all.at[li, row].set(tail)}
            positions = ctx["q_pos"]
        else:                                      # a decode step, by rows
            src, valid, dst = ctx["rows"]
            conv, shifted, seq = cca_conv(cfg, lp, u, v2,
                                          t_all[li][dst, 0])
            tail = jnp.where(valid[:, None, None], seq[src, 1:],
                             t_all[li])
            pool = {**pool, "t": t_all.at[li].set(tail)}
            positions = ctx["pos"][:, None]
    with jax.named_scope("cca_qk_norm"):
        q, k = cca_qk(cfg, lp, u, conv, positions)
    return _attend(cfg, lp, q, k, jnp.concatenate([v1, shifted], axis=-1),
                   pool, li, ctx)


# -- "K": delta-rule linear attention with a decay a channel ------------------

# The published layer's ``l2norm`` epsilon (fla: x * rsqrt(sum x^2 + eps)).
KDA_L2_EPS = 1e-6
# Positions a sub-block of the chunk form: the decay BETWEEN two positions
# is at most 1, but its two halves ``exp(G_i)`` and ``exp(-G_j)`` are not
# bounded over a chunk of a strong decay, so no product is ever split
# across more than one sub-block's own span (``kda_scan``).
KDA_BLOCK = 16


def _kda_qkv(cfg: ModelConfig, u: jax.Array):
    """The convs' output [..., 3 * inner] float32 -> (q, k, v) [..., heads,
    d] float32: queries and keys at unit length a head, the queries times
    ``d^-1/2``."""
    nh, d = cfg.ssm_heads, cfg.ssm_head_dim
    q, k, v = (u[..., j * nh * d:(j + 1) * nh * d].reshape(
        *u.shape[:-1], nh, d) for j in range(3))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + KDA_L2_EPS)
    return unit(q) * d ** -0.5, unit(k), v


def kda_step(cfg: ModelConfig, lp: Params, qkv, g, beta, state, tail,
             valid):
    """The one-step recurrence over ROWS: qkv [R, 3 * inner] the row's
    token before the convs, g [R, heads, d] float32 its log-decay a
    channel, beta [R, heads] float32, state [R, heads, d_k, d_v] float32,
    tail [R, K-1, 3 * inner], valid [R].  Returns (o [R, inner] float32,
    state, tail); a row that is not ``valid`` keeps both bit-identical."""
    with jax.named_scope("kda_conv"):
        u, tail = conv_step(lp, tail, qkv, valid)
    with jax.named_scope("kda_step"):
        q, k, v = _kda_qkv(cfg, u)                           # [R, heads, d]
        decayed = state * jnp.exp(g)[..., None]
        read = jnp.sum(decayed * k[..., None], axis=2)       # S'^T k
        new = decayed + ((beta[..., None] * k)[..., None]
                         * (v - read)[:, :, None, :])
        new = jnp.where(valid[:, None, None, None], new, state)
        o = jnp.sum(new * q[..., None], axis=2)              # S^T q
    return o.reshape(o.shape[0], -1), new, tail


def _unit_lower_inverse(a: jax.Array, c: int) -> jax.Array:
    """``(I + a)^-1`` for ``a`` [..., n, n] strictly lower triangular, ``n``
    = ``c`` times a power of two: the diagonal blocks of ``c`` rows by
    forward substitution (the rows unrolled: no loop is lowered), then
    pairs of blocks merged, ``[[P, 0], [X, Q]]^-1 = [[P^-1, 0], [-Q^-1 X
    P^-1, Q^-1]]``, until one is left.  Every entry is one the sequential
    recurrence forms itself; no power of ``a`` is."""
    lead, n = a.shape[:-2], a.shape[-1]

    def diagonal_blocks(x, m, row, col):
        """Block (2p + row, 2p + col) of ``m`` rows, for every p (row =
        col = 0 with pairs of one: the diagonal blocks)."""
        k = x.shape[-1] // m
        x = x.reshape(*lead, k, m, k, m)
        if row or col:
            x = x.reshape(*lead, k // 2, 2, m, k // 2, 2, m)[
                ..., :, row, :, :, col, :]
        return jnp.moveaxis(jnp.diagonal(x, axis1=-4, axis2=-2), -1, -3)

    diag = diagonal_blocks(a, c, 0, 0)                    # [..., n/c, c, c]
    eye = jnp.eye(c, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (c,))]
    for i in range(1, c):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], jnp.stack(rows, axis=-2),
            precision=HIGHEST))
    inv, m = jnp.stack(rows, axis=-2), c
    while m < n:
        low = diagonal_blocks(a, m, 1, 0)                 # [..., n/2m, m, m]
        pair = inv.reshape(*lead, n // (2 * m), 2, m, m)
        top, bot = pair[..., 0, :, :], pair[..., 1, :, :]
        mixed = -jnp.einsum("...ij,...jk,...kl->...il", bot, low, top,
                            precision=HIGHEST)
        inv = jnp.concatenate(
            [jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
             jnp.concatenate([mixed, bot], axis=-1)], axis=-2)
        m *= 2
    return inv[..., 0, :, :]


def kda_scan(cfg: ModelConfig, lp: Params, qkv, g, beta, state, tail,
             n_valid):
    """The same recurrence over a CHUNK of one sequence, in matrix form:
    qkv [S, 3 * inner], g [S, heads, d], beta [S, heads], from ``state``
    [heads, d_k, d_v] and ``tail`` [K-1, 3 * inner]; positions ``>=
    n_valid`` are right padding — their log-decay and their beta are 0, so
    they neither decay nor feed the state, and the tail is taken from the
    last valid rows.  Returns (o [S, inner] float32, state, tail).  A head
    at a time, with ``G_t`` the running sum of ``g`` within the chunk and
    ``P(x)_ij = sum_d x_id k_jd exp(G_id - G_jd)``:

        A = strict_tril(beta_i P(k)_ij)        T = (I + A)^-1 Diag(beta)
        D = T V - T (K exp G) S_0              (the corrected values)
        O = (Q exp G) S_0 + tril(P(q)) D
        S_end = Diag(exp G_end) S_0 + (K exp(G_end - G))^T D

    ``exp(G_i - G_j) <= 1`` for ``i >= j`` but neither half is bounded, so
    ``P`` is formed by sub-blocks of ``KDA_BLOCK`` positions: a row
    against an EARLIER sub-block's position as ``(x_i exp(G_i - G_b)) .
    (k_j exp(G_b - G_j))`` with ``G_b`` the sum up to the row's own
    sub-block, both factors at most 1; within a sub-block the exponent is
    taken whole.  Every other factor above is at most 1 as written.  All
    products float32 at the highest precision (some 4 GFLOP a layer a
    chunk of 256 at 32 heads of 128)."""
    s_c = qkv.shape[0]
    nh, d, c = cfg.ssm_heads, cfg.ssm_head_dim, KDA_BLOCK
    with jax.named_scope("kda_conv"):
        u, tail = conv_chunk(lp, tail, qkv, n_valid)
    with jax.named_scope("kda_scan"):
        q, k, v = _kda_qkv(cfg, u)                           # [S, heads, d]
        live = jnp.arange(s_c) < n_valid
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        # Whole sub-blocks, a power of two of them (the inverse's merge);
        # the padding is more right padding.
        nb = 1
        while nb * c < s_c:
            nb *= 2
        n = nb * c

        def by_head(x):                          # [S, heads, w] -> [h, n, w]
            x = jnp.pad(x.reshape(s_c, nh, -1), ((0, n - s_c), (0, 0),
                                                 (0, 0)))
            return x.transpose(1, 0, 2)
        q, k, v, g = by_head(q), by_head(k), by_head(v), by_head(g)
        beta = by_head(beta)[..., 0]                             # [h, n]

        def blocks(x):
            return x.reshape(nh, nb, c, -1)
        tril = jnp.tril(jnp.ones((c, c), jnp.float32))
        lower = jnp.tril(jnp.ones((nb, nb), jnp.float32), -1)
        # Every span's log-decay is a SUM of its positions' (never the
        # difference of two running sums: at -400 those leave 1e-5 of a
        # factor that matters).  Within a sub-block up to a position
        # (inclusive) and after it; whole sub-blocks before and after
        # one, and strictly between two.
        local = jnp.einsum("ij,hbjd->hbid", tril, blocks(g),
                           precision=HIGHEST)
        rest = jnp.einsum("ji,hbjd->hbid", tril - jnp.eye(c), blocks(g),
                          precision=HIGHEST)
        whole = local[:, :, -1]                                # [h, nb, d]
        before = jnp.einsum("bc,hcd->hbd", lower, whole, precision=HIGHEST)
        after = jnp.einsum("cb,hcd->hbd", lower, whole, precision=HIGHEST)
        between = jnp.einsum(
            "bcx,hxd->hbcd", lower[:, None, :] * lower.T[None, :, :], whole,
            precision=HIGHEST)                      # blocks x: c < x < b
        cum = (before[:, :, None] + local).reshape(nh, n, d)          # G
        to_end = (after[:, :, None] + rest).reshape(nh, n, d)  # G_end - G
        # Rows of sub-block b against every EARLIER position j, of
        # sub-block c < b: what is left of c after j, and the sub-blocks
        # between.
        gap = jnp.where(lower[None, :, :, None, None] > 0,
                        between[:, :, :, None] + rest[:, None], -jnp.inf)
        k_from = k[:, None] * jnp.exp(gap.reshape(nh, nb, n, d))
        to_block = jnp.exp(local)
        rows = jnp.concatenate([blocks(k) * to_block, blocks(q) * to_block],
                               axis=2)                        # [h, nb, 2c, d]
        across = jnp.einsum("hbid,hbjd->hbij", rows, k_from,
                            precision=HIGHEST)
        # Within a sub-block, the exponent whole: i >= j only.
        inside = jnp.exp(jnp.where(
            tril[:, :, None] > 0,
            local[:, :, :, None] - local[:, :, None], -jnp.inf))
        kb = blocks(k)[:, :, None]                         # [h, nb, 1, c, d]

        def pairs(x, off):
            """P(x) [h, n, n]: zero above the diagonal."""
            near = jnp.sum(blocks(x)[:, :, :, None] * kb * inside, axis=-1)
            near = (near[:, :, :, None, :]
                    * jnp.eye(nb, dtype=jnp.float32)[None, :, None, :, None])
            return (across[:, :, off:off + c]
                    + near.reshape(nh, nb, c, n)).reshape(nh, n, n)
        a = (beta[:, :, None] * pairs(k, 0)
             * jnp.tril(jnp.ones((n, n), jnp.float32), -1))
        t = _unit_lower_inverse(a, c) * beta[:, None, :]
        decay = jnp.exp(cum)
        wu = jnp.einsum("hij,hjd->hid", t,
                        jnp.concatenate([k * decay, v], axis=-1),
                        precision=HIGHEST)
        fixed = wu[..., d:] - jnp.einsum("hid,hde->hie", wu[..., :d], state,
                                         precision=HIGHEST)
        o = (jnp.einsum("hid,hde->hie", q * decay, state, precision=HIGHEST)
             + jnp.einsum("hij,hje->hie", pairs(q, c), fixed,
                          precision=HIGHEST))
        new = (decay[:, -1, :, None] * state
               + jnp.einsum("hjd,hje->hde", k * jnp.exp(to_end), fixed,
                            precision=HIGHEST))
    return o.transpose(1, 0, 2)[:s_c].reshape(s_c, -1), new, tail


def _kda(cfg: ModelConfig, lp: Params, h_in, pool, li, ctx):
    """h_in [B, S, H] -> (mixer output, pool); ``li`` the layer's index
    among the "K" layers.  ``pool["s"]`` [layers, R, heads, d_k, d_v]
    float32, ``pool["t"]`` [layers, R, K-1, 3 * inner]."""
    nh, d = cfg.ssm_heads, cfg.ssm_head_dim
    with jax.named_scope("kda_proj"):
        qkv = jnp.concatenate([quant.matmul(h_in, lp[key])
                               for key in ("wq", "wk", "wv")], axis=-1)
    with jax.named_scope("kda_gate"):
        # The decay compounds through every later position of the state:
        # its two small projections and beta's in float32 at the highest
        # precision, as ``_time_step``'s.
        def f32(spec, x, w):
            return jnp.einsum(spec, x.astype(jnp.float32),
                              w.astype(jnp.float32), precision=HIGHEST)
        f = f32("...r,rc->...c", f32("...h,hr->...r", h_in, lp["w_fa"]),
                lp["w_fb"])
        g = (-jnp.exp(lp["a_log"])[:, None]
             * jax.nn.softplus(f + lp["dt_bias"]).reshape(*f.shape[:-1],
                                                          nh, d))
        beta = jax.nn.sigmoid(f32("...h,hn->...n", h_in, lp["w_beta"]))
        z = quant.matmul(quant.matmul(h_in, lp["w_ga"]), lp["w_gb"])
    o, pool = through_rows(
        pool, li, ctx,
        lambda state, tail, n: kda_scan(cfg, lp, qkv[0], g[0], beta[0],
                                        state, tail, n),
        lambda src, state, tail, valid: kda_step(
            cfg, lp, qkv[src, 0], g[src, 0], beta[src, 0], state, tail,
            valid), "kda")
    with jax.named_scope("kda_out_norm"):
        # An RMSNorm a head under ONE gain, times the sigmoid gate.
        o = o.reshape(*o.shape[:-1], nh, d)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.norm_eps) * lp["gn"].astype(jnp.float32)
        o = (o.reshape(z.shape)
             * jax.nn.sigmoid(z.astype(jnp.float32))).astype(h_in.dtype)
    return quant.matmul(o, lp["wo"]), pool


def _latent(cfg: ModelConfig, lp: Params, h_in, pool, li, ctx):
    """h_in [B, S, H] -> (mixer output, pool); ``li`` the layer's index
    among the "L" layers, which are ``pool["c"]``'s.  The latent family's
    own attention, absorbed for a decode step, up-projected over the
    table's window for a chunk; rotated by ``ctx["rope"]``, the sin and
    cos of the tokens' positions, where the pattern states rotary
    (``forward_paged`` makes them once a program), else not at all."""
    chunk = "row" in ctx
    if chunk:
        bs = pool["c"].shape[2]
        tables, q_pos = ctx["table"][None, :ctx["window"] // bs], ctx["q_pos"]
    else:
        tables, q_pos = ctx["tables"], ctx["pos"][:, None]
    sin, cos = ctx.get("rope", (None, None))
    out, rows = latent_moe._attend(cfg, lp, h_in, sin, cos, q_pos,
                                   pool["c"], li, ctx["blk"], ctx["off"],
                                   tables, absorbed=not chunk)
    return quant.matmul(out, lp["wo"]), {**pool, "c": rows}


def _relu2_mlp(x, up, down):
    a = jax.nn.relu(quant.matmul(x, up))
    return quant.matmul(a * a, down)


def shared_expert(lp: Params, x: jax.Array) -> jax.Array:
    """The expert every token goes through, of the routed experts' form:
    gated where the layer holds a gate for it."""
    with jax.named_scope("shared_expert"):
        if "ws_gate" in lp:
            return transformer._swiglu(x, lp["ws_gate"], lp["ws_up"],
                                       lp["ws_down"])
        return _relu2_mlp(x, lp["ws_up"], lp["ws_down"])


def route_mlp(cfg: ModelConfig, lp: Params, x: jax.Array, carry):
    """The router of ``cfg.router_hidden``: x [T, H] float32, carry [T,
    router_hidden] float32 the router's state of the SAME tokens in the
    expert layer before (zeros before the first) -> (choice [T, k] int32,
    weight [T, k] float32, this layer's state).  ``r = x W_r + gain *
    carry``; the scores are an MLP's over ``RMSNorm(r)`` (two GELU layers
    of the state's width, then the outputs), softmax in float32; the top
    ``experts_per_token`` of probability + bias are chosen (the bias
    moves the CHOICE only) and weighed by their probabilities as they
    are: nothing renormalises, nothing scales."""
    def matmul(a, w):
        return jnp.einsum("tr,re->te", a, w.astype(jnp.float32),
                          precision=HIGHEST)
    r = matmul(x.astype(jnp.float32), lp["router"]) \
        + lp["router_carry"] * carry
    z = _norm_f32(r, lp["router_ln"], cfg.norm_eps)
    for name in ("router_w1", "router_w2"):
        z = jax.nn.gelu(matmul(z, lp[name]), approximate=False)
    p = jax.nn.softmax(matmul(z, lp["router_w3"]), axis=-1)
    _, choice = jax.lax.top_k(p + lp["router_bias"], cfg.experts_per_token)
    return (choice.astype(jnp.int32),
            jnp.take_along_axis(p, choice, axis=1), r)


def routed_experts(cfg: ModelConfig, lp: Params, x: jax.Array,
                   stacked: Optional[Params] = None, period=None,
                   carry=None):
    """x [T, H] float32 -> (the HELD experts' part of the routed sum [T, H]
    in the model's dtype, counts [experts_held + 1] int32: assignments a
    held expert, then those that went to absent ones, the router's state
    to carry to the next expert layer).  The router scores
    all ``num_experts`` outputs and weighs the chosen over ALL of them,
    whoever holds them: the sigmoid router of one matrix
    (``latent_moe.route``, which carries nothing: ``carry`` comes back as
    it went), or the MLP router over its carried state where
    ``cfg.router_hidden``.  Dropless and sorted by expert as
    ``latent_moe.routed_experts``; ``stacked`` [periods, held, in, out]
    with ``period`` the traced index, for the same reason as there."""
    t, h = x.shape
    k, held = cfg.experts_per_token, cfg.experts_held
    xd = x.astype(jnp.dtype(cfg.dtype))
    with jax.named_scope("moe_router"):
        if cfg.router_hidden:
            choice, w, carry = route_mlp(cfg, lp, x, carry)
        else:
            choice, w = latent_moe.route(cfg, lp, x)
        local = choice - cfg.experts_first
        # Absent experts sort last, as one group nothing multiplies.
        flat = jnp.where((local >= 0) & (local < held), local,
                         held).reshape(-1)
        counts = jnp.sum(flat[:, None] == jnp.arange(held + 1), axis=0,
                         dtype=jnp.int32)
        order = jnp.argsort(flat, stable=True)
        mats, sizes = lp, counts[:held]
        if stacked is not None:
            n = stacked[EXPERT_KEYS[-1]].shape[0]
            mats = {key: w_.reshape(n * held, *w_.shape[2:])
                    for key, w_ in stacked.items()}
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros(n * held, jnp.int32), sizes, (period * held,))
    with jax.named_scope("moe_experts"):
        expert = flat[order]
        here = (expert < held)[:, None]
        group = jnp.minimum(expert, held - 1)
        xs = xd[order // k]                                    # [T*k, H]
        xs = jnp.pad(xs, ((0, 0), (0, expert_dims_stored(cfg)[0] - h)))
        y = latent_moe.expert_ffn(xs, mats.get("we_gate"), mats["we_up"],
                                  mats["we_down"], sizes, group)[:, :h]
        # Rows past the held groups belong to no group: whatever the
        # grouped product left there is not a number of this layer.
        y = jnp.where(here, y, 0)
        y = y[jnp.argsort(order)].reshape(t, k, h)
        out = jnp.einsum("tkh,tk->th", y.astype(jnp.float32), w)
    return out.astype(xd.dtype), counts, carry


def _experts(cfg: ModelConfig, lp: Params, h_f32, stacked, period, carry):
    """h_f32 [B, S, H], the float32 normed input, carry [B, S,
    router_hidden] -> (output in the model's dtype, counts, carry)."""
    b, s, h = h_f32.shape
    out, counts, carry = routed_experts(
        cfg, lp, h_f32.reshape(b * s, h), stacked, period,
        carry.reshape(b * s, -1))
    out = out.reshape(b, s, h)
    if "ws_up" in lp:
        out = out + shared_expert(lp, h_f32.astype(out.dtype))
    return out, counts, carry.reshape(b, s, -1)


def _merge(lp: Params, x, out):
    """The residual after a sublayer: the sum, or — where the layer holds
    the four vectors of the scaled merge — ``(a_r x + b_r) + (a_o out +
    b_o)`` in float32, rounded to the residual's dtype."""
    if "res" not in lp:
        return x + out
    a_r, b_r, a_o, b_o = lp["res"]
    return ((a_r * x + b_r) + (a_o * out + b_o)).astype(x.dtype)


# =============================================================================
# The forward pass over the paged pool
# =============================================================================

def _norm_f32(x, w, eps):
    """RMSNorm with the gain, in float32 (the router reads this; the
    mixers its rounding to the model's dtype)."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def forward_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  pool, ctx: Dict[str, Any]):
    """tokens [B, S]; ``pool`` {"k", "v": [attention layers, NB, bs,
    N_kv * D] (a pattern with "L": "c" [latent layers, NB, bs,
    kv_lora_rank + qk_rope_head_dim] alone), "s": [state-space layers, R,
    heads, P, N] float32 (Mamba-1: [.., R, state, inner]; "K": [.., R,
    heads, d_k, d_v]), "t": [state-space layers, R, K-1, C] ("K": C the
    three convs' channels; a pattern with "C": ["C" layers, R, 1,
    cca_tail_width], and "s" empty), "owner": [R]}.  ``ctx`` is what the
    mixers need of where the tokens sit (``chunk_ctx`` / ``decode_ctx``).
    Returns (hidden [B, S, H] after the final norm, pool, counts [expert
    layers, experts_held + 1])."""
    dtype = jnp.dtype(cfg.dtype)
    lead, period = cfg.layer_lead, cfg.layer_period
    index = {kind: kind_index(cfg, kind) for kind in KINDS}
    x = quant.embed_rows(params["embed"], tokens).astype(dtype)
    if cfg.rotary and "L" in cfg.layer_pattern:
        # Every "L" layer rotates by the same positions: a chunk's, or a
        # decode step's one a sequence.
        with jax.named_scope("step_inputs"):
            ctx = {**ctx, "rope": latent_moe.rope_sincos(
                cfg, ctx["q_pos"] if "row" in ctx else ctx["pos"][:, None])}
    owner = pool["owner"]
    carried = {key: a for key, a in pool.items() if key != "owner"}

    def sublayer(kind, lp, li, x, carried, routed, stacked=None, p=None):
        """One sublayer of ``kind``, the ``li``-th of its kind -> (x,
        carried, routed, the counts of an "E" or None)."""
        counts = None
        # Whatever of a sublayer no scope of its own names (its norm, a
        # projection outside ``*_proj``, the merge) is the feed-forward's
        # or the mixer's.
        with jax.named_scope("ffn" if kind in "-E" else "mixer_proj"):
            h_f32 = _norm_f32(x, lp["ln"], cfg.norm_eps)
            if kind == "M" and cfg.ssm_dt_rank:
                out, carried, _ = mamba1(cfg, lp, h_f32.astype(dtype),
                                         carried, li, ctx)
            elif kind == "-":
                out = transformer._swiglu(h_f32.astype(dtype), lp["w_gate"],
                                          lp["w_up"], lp["w_down"])
            elif kind == "E":
                out, counts, routed = _experts(cfg, lp, h_f32, stacked, p,
                                               routed)
            else:
                mixer = {"M": _mamba, "*": _attention, "C": _cca, "K": _kda,
                         "L": _latent}[kind]
                out, carried = mixer(cfg, lp, h_f32.astype(dtype), carried,
                                     li, ctx)
            return _merge(lp, x, out), carried, routed, counts

    # The MLP router's state of every token, carried from expert layer to
    # expert layer (zero-wide under the router that carries nothing).
    routed = jnp.zeros(tokens.shape + (cfg.router_hidden,), jnp.float32)
    # The pattern's lead sublayers run inline, so the periods' scan stays
    # the program's one layer loop.
    lead_counts = []
    for j, kind in enumerate(lead):
        x, carried, routed, n = sublayer(kind, params["lead"][j],
                                         lead[:j].count(kind), x, carried,
                                         routed)
        if n is not None:
            lead_counts.append(n)

    # The experts' matrices stay OUT of what the loop slices a period
    # (``latent_moe.routed_experts``); int8 ones are widened a layer at a
    # time.
    layers = [dict(lp) for lp in params["periods"]]
    stacked = [None] * len(period)
    for j, kind in enumerate(period):
        if kind == "E" and not quant.is_quantized(layers[j]["we_up"]):
            stacked[j] = {key: layers[j].pop(key) for key in EXPERT_KEYS
                          if key in layers[j]}

    def body(carry, scanned):
        x, carried, routed = carry
        lps, p = scanned
        counts = []
        for j, kind in enumerate(period):
            before, per_period = index[kind]
            x, carried, routed, n = sublayer(
                kind, lps[j], lead.count(kind) + p * per_period + before[j],
                x, carried, routed, stacked[j], p)
            if n is not None:
                counts.append(n)
        return (x, carried, routed), jnp.stack(counts) if counts else None

    n_periods = (cfg.num_layers - len(lead)) // len(period)
    with jax.named_scope("layer_scan"):
        (x, carried, _), counts = jax.lax.scan(
            body, (x, carried, routed), (layers, jnp.arange(n_periods)))
    with jax.named_scope("head"):
        hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    parts = [n[None] for n in lead_counts]
    if counts is not None:
        parts.append(counts.reshape(-1, counts.shape[-1]))
    counts = (jnp.concatenate(parts) if parts else
              jnp.zeros((0, cfg.experts_held + 1), jnp.int32))
    return hidden, {**carried, "owner": owner}, counts


def chunk_ctx(pool, table, start, true_len, s_c: int, window: int,
              blk, off, q_pos):
    """One sequence's chunk: claims (``start == 0``) or finds its row.
    Returns (ctx, the pool with the row named)."""
    with jax.named_scope("step_inputs"):
        fresh = start[0] == 0
        row, owner = claim_row(pool["owner"], table[0], fresh)
        return {"row": row, "fresh": fresh,
                "n_valid": jnp.clip(true_len[0] - start[0], 0, s_c),
                "table": table, "start": start, "q_pos": q_pos,
                "window": window, "blk": blk[None], "off": off[None]}, {
                    **pool, "owner": owner}


def decode_ctx(pool, tables, pos, blk, off):
    with jax.named_scope("step_inputs"):
        return {"rows": rows_of(pool["owner"], tables[:, 0]),
                "tables": tables, "pos": pos, "blk": blk[:, None],
                "off": off[:, None]}
