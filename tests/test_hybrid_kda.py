"""The hybrid family's fourth pattern (models/hybrid_ssm.py: delta-rule
linear attention with a decay a channel — a float32 matrix a head and
three conv tails a slot — three layers in four, latent attention without
rotary over a paged latent row in the fourth, a dense lead layer ahead of
the one layer loop, sigmoid-routed gated experts of which this share holds
half, an untied head: Moonshot's Kimi-Linear block) against its plain
float32 reference (benchmark/reference/kda_latent_moe_decoder.py, which
imports nothing of the program), at the tiny ``hybrid_kda_test`` preset:
"K-" and two periods of "KEKELEKE".

(1) system against reference on logits, prefill in chunks then decode
through rows and the latent pool; (2) the chunk form against the step
form, position by position, at decays across the init's range and at one
so strong that the running log-decay passes -80 inside a chunk; (3) each
named part of the mathematics dropped in turn from the reference fails
the tolerance by a stated multiple; (4) the four shares add up to the
uncut layer; (5) the rows: claimed and zeroed, an idle slot's and a
prefilling slot's left bit-identical; (6) the state is float32; (7) what
the family refuses, by name; then the engine, its programs and /stats,
and configuration, pool, roofline, int8.
"""

import dataclasses
import glob
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu import models
from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
from distributed_llm_tpu.engine import paged_kv
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.models import hybrid_ssm, transformer
from test_latent_moe import _while_depth

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The keys of the tiny preset, as the reference reads them.
TINY = {
    "hidden_size": 64, "num_hidden_layers": 9, "vocab_size": 512,
    "intermediate_size": 96, "first_k_dense_replace": 1,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7, 9],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts": 4, "router_outputs": 8, "first_routed_expert": 0,
    "num_experts_per_token": 3, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "moe_renormalize": True, "rms_norm_eps": 1e-5, "torch_dtype": "float32",
}
SEED = 5
BLOCK = 16
TABLE = jnp.arange(1, 9, dtype=jnp.int32)
# 100 ids: 72 of prompt and 28 decode steps: the latent rows cross six
# block boundaries, the state and the tails every chunk edge and step.
TOKENS = np.random.default_rng(0).integers(0, 500, 100).astype(np.int32)
N_PROMPT = 72
# Float32 against float32: what the two orders of operations leave (the
# chunk's matrix form against the reference's token-by-token scan).
F32_TOL = 2e-5


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "kda_latent_moe_reference", os.path.join(
            ROOT, "benchmark", "reference", "kda_latent_moe_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load_reference()


def _cfg(dtype="float32", **kw):
    return dataclasses.replace(MODEL_PRESETS["hybrid_kda_test"],
                               dtype=dtype, **kw)


def _params(cfg, seed=SEED):
    return jax.jit(lambda s: models.init_params(cfg, s))(jnp.int32(seed))


def _pool(cfg, slots=2):
    return paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=BLOCK, max_slots=slots, max_seq_len=128))


_CHUNK_FNS = {}


def _chunk_fn(cfg, window):
    if (cfg, window) not in _CHUNK_FNS:
        _CHUNK_FNS[cfg, window] = jax.jit(
            lambda params, pool, piece, start, total, table:
            paged_kv.chunk_prefill_paged(cfg, params, piece, start, total,
                                         pool, table, window))
    return _CHUNK_FNS[cfg, window]


def _prefill(cfg, params, pool, tok, table=TABLE, chunk=16, pad=0,
             windows=(128,)):
    """``tok`` through the chunk program, the last chunk right-padded
    with ``pad``, each chunk at the smallest of ``windows`` that holds
    its end.  Returns (the last valid position's hidden, pool)."""
    for start in range(0, len(tok), chunk):
        piece = np.full((1, chunk), pad, np.int32)
        k = min(chunk, len(tok) - start)
        piece[0, :k] = tok[start:start + k]
        window = next(w for w in windows if w >= start + chunk)
        hidden, pool = _chunk_fn(cfg, window)(
            params, pool, jnp.asarray(piece), jnp.array([start]),
            jnp.array([len(tok)]), table)
    return hidden[0, k - 1], pool


def _serve(cfg, params, tok=TOKENS, n_prompt=N_PROMPT, **kw):
    """Chunked prefill of ``tok[:n_prompt]`` then teacher-forced decode of
    the rest, the sequence in batch slot 1 beside an idle slot 0; logits
    at positions n_prompt-1 ..."""
    last, pool = _prefill(cfg, params, _pool(cfg), tok[:n_prompt], **kw)
    out = [transformer.logits_from_hidden(params, last)]
    tables = jnp.stack([jnp.zeros(8, jnp.int32), TABLE])
    step = jax.jit(lambda params, pool, cur, pos: paged_kv.decode_step_paged(
        cfg, params, cur, pos, pool, tables))
    for p in range(n_prompt, len(tok)):
        logits, pool = step(params, pool, jnp.asarray([0, tok[p]]),
                            jnp.array([0, p]))
        out.append(logits[1])
    return np.stack([np.asarray(x, np.float32) for x in out])


def _reference(ref, model=TINY, tok=TOKENS, n_prompt=N_PROMPT, seed=SEED):
    return np.asarray(ref.logits(
        model, ref.init_weights(model, seed), jnp.asarray(tok[None]),
        jnp.arange(n_prompt - 1, len(tok))[None]))[0]


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def want(ref):
    return _reference(ref)


@pytest.fixture(scope="module")
def got():
    cfg = _cfg()
    return _serve(cfg, _params(cfg))


# (1) against the reference ------------------------------------------------------

def test_float32_chunks_then_decode_match_the_reference(ref, got, want):
    assert ref.pattern(TINY) == _cfg().layer_pattern == "K-" + "KEKELEKE" * 2
    assert "head" in _params(_cfg())                    # untied
    assert got.shape == want.shape == (len(TOKENS) - N_PROMPT + 1, 512)
    assert _rel(got, want) < F32_TOL


@pytest.mark.parametrize("chunk", [7, 24, 72])
def test_chunk_edges_anywhere_give_the_same_numbers(chunk, got, want):
    # The state and the three tails cross every edge: 7 cuts the prompt
    # at odd and even positions and pads its last chunk (and the chunk
    # form pads each to a sub-block), 24 is a sub-block and a half, 72
    # the prompt whole (padded to 128 positions inside the form).
    cfg = _cfg()
    other = _serve(cfg, _params(cfg), chunk=chunk)
    assert _rel(other, want) < F32_TOL
    np.testing.assert_allclose(other, got, rtol=0, atol=3e-5)


def test_the_first_tokens_meet_zero_tails_and_a_zero_state(ref):
    # Positions 0-2 read zeros through the convs' four taps.
    cfg = _cfg()
    params = _params(cfg)
    for n in (1, 3):
        last, _ = _prefill(cfg, params, _pool(cfg), TOKENS[:n], chunk=4)
        logits = np.asarray(transformer.logits_from_hidden(params, last))
        assert _rel(logits, _reference(ref, TINY, TOKENS[:n], n)[0]) < F32_TOL


def test_bfloat16_chunks_then_decode_stay_within_its_rounding(ref):
    cfg = _cfg("bfloat16")
    out = _serve(cfg, _params(cfg))
    full = _reference(ref, {**TINY, "torch_dtype": "bfloat16"})
    # bfloat16 weights on both sides; the system also rounds activations
    # (2^-9 a rounding through 18 sublayers) and a top-3 choice made by a
    # hair may flip, the reference none.  Read at this seed: 6.7e-3.
    assert 1e-3 < _rel(out, full) < 4e-2


# (2) the chunk form against the step form -----------------------------------------

def _steps(cfg, lp, qkv, g, beta, state, tail, n_valid, at_rest=None):
    """The step form over the first ``n_valid`` positions, one at a time;
    ``at_rest`` is what the state is stored as between two steps."""
    step = jax.jit(lambda *a: hybrid_ssm.kda_step(cfg, lp, *a))
    s, t, outs = state[None], tail[None], []
    for i in range(n_valid):
        o_i, s, t = step(qkv[i][None], g[i][None], beta[i][None], s, t,
                         jnp.array([True]))
        if at_rest is not None:
            s = s.astype(at_rest).astype(jnp.float32)
        outs.append(o_i[0])
    return np.stack(outs), np.asarray(s[0]), np.asarray(t[0])


def _scan_and_steps(cfg, scale, n=80, n_valid=75, seed=0, at_rest=None):
    """One layer's recurrence over ``n`` positions (``n_valid`` of them
    valid) from a random state: the chunk form's outputs and end state,
    and the step form's position by position; ``scale`` times a
    log-decay a channel drawn in [-1, -0.01]."""
    nh, d = cfg.ssm_heads, cfg.ssm_head_dim
    lp = hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(SEED), "K")
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    qkv = jax.random.normal(ks[0], (n, 3 * nh * d), jnp.float32)
    g = -scale * jax.random.uniform(ks[1], (n, nh, d), jnp.float32, 0.01, 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (n, nh)))
    state = 0.3 * jax.random.normal(ks[3], (nh, d, d))
    tail = jax.random.normal(ks[4], (cfg.ssm_conv - 1, 3 * nh * d))
    o, end, t_end = jax.jit(
        lambda *a: hybrid_ssm.kda_scan(cfg, lp, *a))(
            qkv, g, beta, state, tail, n_valid)
    outs, s, t = _steps(cfg, lp, qkv, g, beta, state, tail, n_valid, at_rest)
    return (np.asarray(o), np.asarray(end), np.asarray(t_end), outs, s, t,
            float(jnp.cumsum(g[:n_valid], axis=0).min()))


@pytest.mark.parametrize("scale,passes_80", [
    # The init's range: a log-decay a token of -0.001 .. -1.6.
    (0.002, False), (0.1, False), (1.6, False),
    # Far past it: the running sum passes -80 within a sub-block or two
    # (-135 in the first sub-block, -400 to -540 by the chunk's end), where exp(-G) alone overflows
    # float32.
    (12.0, True)])
def test_the_chunk_form_equals_the_step_form_position_by_position(
        scale, passes_80):
    o, end, t_end, outs, s, t, g_min = _scan_and_steps(_cfg(), scale)
    assert (g_min < -80.0) is passes_80
    assert np.isfinite(o).all() and np.isfinite(end).all()
    # Float32 on both sides, outputs of size 0.2 and states of 0.5: the
    # two orders of summation.  Padded positions touch neither.
    np.testing.assert_allclose(o[:75], outs, atol=2e-6, rtol=0)
    np.testing.assert_allclose(end, s, atol=2e-6, rtol=0)
    np.testing.assert_array_equal(t_end, t)


def test_the_unit_lower_inverse_is_the_inverse():
    rng = np.random.default_rng(1)
    a = np.tril(rng.normal(size=(3, 64, 64)), -1).astype(np.float32)
    inv = np.asarray(hybrid_ssm._unit_lower_inverse(jnp.asarray(a), 16))
    np.testing.assert_allclose(inv @ (np.eye(64) + a),
                               np.broadcast_to(np.eye(64), a.shape),
                               atol=2e-3 * np.abs(inv).max(), rtol=0)
    assert np.abs(np.triu(inv, 1)).max() == 0.0


# (3) each part of the mathematics, dropped from the reference -------------------

def _without(ref, what, monkeypatch):
    """The reference's logits with one named term left out (or, for the
    rotary, wrongly put in)."""
    model = dict(TINY)
    _patch(ref, what, monkeypatch, model)
    return _reference(ref, model)


def _patch(ref, what, monkeypatch, model):
    if what == "beta":
        monkeypatch.setattr(ref, "beta_of", lambda model, w, x: jnp.ones(
            (x.shape[0], model["linear_attn_config"]["num_heads"])))
    elif what == "the decay a channel":
        per_channel = ref.log_decay
        monkeypatch.setattr(
            ref, "log_decay", lambda model, w, x: jnp.broadcast_to(
                per_channel(model, w, x).mean(-1, keepdims=True),
                per_channel(model, w, x).shape))
    elif what == "the l2 norms":
        monkeypatch.setattr(ref, "l2norm", lambda x: x)
    elif what == "the conv on v":
        conv = ref.short_conv
        monkeypatch.setattr(
            ref, "short_conv", lambda x, taps, which:
            jax.nn.silu(x) if which == "v" else conv(x, taps, which))
    elif what == "the output gate":
        monkeypatch.setattr(ref, "output_gate", lambda model, w, x: 1.0)
    elif what == "the output norm":
        monkeypatch.setattr(ref, "head_norm", lambda o, eps: o)
    elif what == "k_pe":
        plain = ref.latent_qkv

        def no_k_pe(model, w, x):
            q, k_nope, k_pe, v = plain(model, w, x)
            return q, k_nope, jnp.zeros_like(k_pe), v
        monkeypatch.setattr(ref, "latent_qkv", no_k_pe)
    elif what == "a rotary wrongly applied":
        plain = ref.latent_qkv

        def rotated(model, w, x):
            q, k_nope, k_pe, v = plain(model, w, x)
            dn = model["qk_nope_head_dim"]
            sin, cos = transformer.rope_sincos(
                jnp.arange(x.shape[0]), model["qk_rope_head_dim"], 10000.0)
            q_pe = transformer.apply_rope(q[..., dn:], sin, cos)
            k_pe = transformer.apply_rope(k_pe[:, None], sin, cos)[:, 0]
            return jnp.concatenate([q[..., :dn], q_pe], -1), k_nope, k_pe, v
        monkeypatch.setattr(ref, "latent_qkv", rotated)
    elif what == "the choice-only bias":
        monkeypatch.setattr(ref, "ROUTER_BIAS_STD", 0.0)
    elif what == "the renormalisation":
        model["moe_renormalize"] = False
    elif what == "2.446":
        model["routed_scaling_factor"] = 1.0
    elif what == "the shared expert":
        model["num_shared_experts"] = 0


# The least multiple of the float32 tolerance each dropped term moves the
# logits by; read at this seed: beta 3351, the decay a channel 4264, the l2
# norms 7224, the conv on v 11 554, the output gate 7568, the output norm
# 7706, the choice-only bias 108, the renormalisation 126, 2.446 123, the
# shared expert 207; k_pe 2.8 and a rotary 2.4, because at these widths a
# matrix of normal(0, 0.02) leaves the latent layers' scores near 0.01 and
# their softmax near uniform (test_a_latent_layer_alone_at_scores_of_order_
# one holds both to 3000 where the scores are of order one, as at the
# published widths).  (The experts' three small matrices in a row add
# little beside a residual of size one.)
DROPPED = {"beta": 2000, "the decay a channel": 2500, "the l2 norms": 4000,
           "the conv on v": 6000, "the output gate": 4000,
           "the output norm": 4000, "k_pe": 2,
           "a rotary wrongly applied": 2, "the choice-only bias": 60,
           "the renormalisation": 80, "2.446": 80,
           "the shared expert": 120}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_a_dropped_term_fails_the_float32_tolerance(what, got, monkeypatch):
    wrong = _without(_load_reference(), what, monkeypatch)
    assert _rel(got, wrong) > DROPPED[what] * F32_TOL


def test_a_latent_layer_alone_at_scores_of_order_one(ref, monkeypatch):
    # At the tiny preset's widths a matrix of normal(0, 0.02) leaves the
    # scores near 0.01 and the softmax near uniform, so k_pe and a
    # rotary move the logits by 2-3 tolerances above.  Here ONE "L"
    # layer alone with its query and cache projections 16 times larger
    # (scores of order one, as the published widths give them): a chunk
    # of 24 then 8 steps through the latent pool against the reference's
    # full attention.
    cfg = _cfg()
    lp = hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(SEED), "L")
    lp = dict(lp, wq=16 * lp["wq"], w_kva=16 * lp["w_kva"])
    x = jnp.asarray(np.random.default_rng(6).normal(size=(32, 64)),
                    jnp.float32)
    pool = {"c": jnp.zeros((2, 9, BLOCK, 32), jnp.float32)}
    pos = jnp.arange(32)
    blk, off = TABLE[pos // BLOCK], pos % BLOCK
    out, pool = hybrid_ssm._latent(cfg, lp, x[None, :24], pool, 1, {
        "row": 0, "table": TABLE, "window": 32, "q_pos": pos[None, :24],
        "blk": blk[None, :24], "off": off[None, :24]})
    outs = [out[0]]
    for p in range(24, 32):
        out, pool = hybrid_ssm._latent(cfg, lp, x[None, p:p + 1], pool, 1, {
            "tables": TABLE[None, :2], "pos": pos[p:p + 1],
            "blk": blk[None, p:p + 1], "off": off[None, p:p + 1]})
        outs.append(out[0])
    got = np.concatenate([np.asarray(o) for o in outs])
    assert float(jnp.abs(pool["c"][0]).max()) == 0.0       # layer 1's rows
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.latent_attention(TINY, lp, x))
        assert _rel(got, want) < F32_TOL
        for what, least in (("k_pe", 3000), ("a rotary wrongly applied",
                                             3000)):
            with monkeypatch.context() as patch:
                _patch(ref, what, patch, dict(TINY))
                wrong = np.asarray(ref.latent_attention(TINY, lp, x))
            assert _rel(got, wrong) > least * F32_TOL, what


def test_the_terms_a_test_drops_are_drawn_away_from_their_trivial_values():
    params = _params(_cfg())
    kda = params["periods"][0]
    # A channel's decay differs from its head's mean; betas are not 1.
    assert np.asarray(kda["dt_bias"]).reshape(2, 4, 16).std(-1).min() > 0.3
    assert np.abs(np.asarray(kda["w_beta"])).max() > 0.01
    assert np.abs(np.asarray(params["periods"][1]["router_bias"])).max() \
        > 1e-3
    assert "ws_gate" in params["periods"][1]


# (4) the shares ---------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_references_whole_layer(ref):
    whole = _cfg(experts_first=0, experts_count=8)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 64)),
                    jnp.float32)
    key = jax.random.PRNGKey(SEED)
    lp = hybrid_ssm.init_layer(whole, key, "E")
    parts, counts = [], []
    for first in (0, 2, 4, 6):
        cfg = _cfg(experts_first=first, experts_count=2)
        share = hybrid_ssm.init_layer(cfg, key, "E")
        # An expert's matrix is the same whichever share holds it.
        for name in hybrid_ssm.EXPERT_KEYS:
            np.testing.assert_array_equal(
                np.asarray(share[name]), np.asarray(lp[name][first:first + 2]))
        # The sigmoid router carries nothing: a zero-wide state.
        out, n, _ = hybrid_ssm._experts(cfg, share, x[None], None, None,
                                        jnp.zeros((1, x.shape[0], 0)))
        parts.append(np.asarray(out[0]))
        counts.append(np.asarray(n))
    shared = np.asarray(hybrid_ssm.shared_expert(lp, x))
    model = dict(TINY, num_experts=8)
    # The program stores the experts' matrices zero-padded to multiples
    # of 256; the reference takes them at the published sizes.
    cut = dict(lp, we_gate=lp["we_gate"][:, :64, :32],
               we_up=lp["we_up"][:, :64, :32],
               we_down=lp["we_down"][:, :32, :64])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts_layer(model, cut, x))
        routed = np.asarray(ref.experts_layer(
            model, {k: v for k, v in cut.items()
                    if not k.startswith("ws_")}, x))
    # Experts 0-1, 2-3, 4-5 and 6-7, the shared expert (which every rank
    # computes alike) counted once: the whole layer.  Float32 sums in
    # another order: 1e-5 of outputs of size 1.
    np.testing.assert_allclose(sum(parts) - 3 * shared, want, atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(want - routed, shared, atol=1e-5, rtol=0)
    # Every assignment is held by exactly one share: 12 tokens x 3.
    assert sum(c[:2].sum() for c in counts) == 36
    for c in counts:
        assert c[:2].sum() + c[2] == 36


# (5) the rows -----------------------------------------------------------------------

def test_padding_an_idle_slot_and_a_prefilling_slot_leave_rows_bit_identical():
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:40]
    _, a = _prefill(cfg, params, _pool(cfg), tok, pad=0)
    _, b = _prefill(cfg, params, _pool(cfg), tok, pad=77)
    # What the 8 padded positions of the last chunk hold reaches neither
    # the state nor the tails.  Bit for bit.
    for key in ("s", "t", "owner"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))
    # The chunk with start == 0 claimed row 0 for block 1; row 1 is free
    # and zero.
    assert np.asarray(a["owner"]).tolist() == [1, 0]
    assert np.abs(np.asarray(a["s"][:, 0])).max() > 0
    assert np.abs(np.asarray(a["s"][:, 1])).max() == 0

    other = jnp.arange(9, 17, dtype=jnp.int32)
    pool = paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=BLOCK, max_slots=3, max_seq_len=128, pool_blocks=24))
    _, pool = _prefill(cfg, params, pool, tok)
    _, pool = _prefill(cfg, params, pool, tok[:20][::-1].copy(), table=other)
    assert np.asarray(pool["owner"]).tolist() == [1, 9, 0]
    # A tick of four steps: the first sequence is still in prefill (its
    # table is all trash in these steps), the third slot is idle, the
    # second decodes.
    tables = jnp.stack([jnp.zeros(8, jnp.int32), other,
                        jnp.zeros(8, jnp.int32)])
    after = pool
    for p in range(20, 24):
        _, after = paged_kv.decode_step_paged(
            cfg, params, jnp.asarray([5, 6, 7]), jnp.array([p, p, 0]),
            after, tables)
    for key in ("s", "t"):
        for row in (0, 2):
            np.testing.assert_array_equal(np.asarray(after[key][:, row]),
                                          np.asarray(pool[key][:, row]))
        assert not np.array_equal(np.asarray(after[key][:, 1]),
                                  np.asarray(pool[key][:, 1]))


def test_a_sequence_admitted_into_a_used_row_starts_from_zero():
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:32]
    clean, fresh = _prefill(cfg, params, _pool(cfg), tok)
    _, used = _prefill(cfg, params, _pool(cfg), TOKENS[40:72])
    # The same blocks again (the allocator hands a finished sequence's
    # blocks on): the chunk with start == 0 finds the row and zeroes it.
    again, used = _prefill(cfg, params, used, tok)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(clean))
    for key in ("s", "t"):
        np.testing.assert_array_equal(np.asarray(used[key]),
                                      np.asarray(fresh[key]))


# (6) the state is float32 -----------------------------------------------------------

def test_the_state_is_float32_at_rest_and_a_bfloat16_state_fails(ref):
    cfg = _cfg("bfloat16")
    params = _params(cfg)
    pool = _pool(cfg)
    assert pool["s"].dtype == jnp.float32 and pool["t"].dtype == jnp.bfloat16
    _, pool = _prefill(cfg, params, pool, TOKENS[:32])
    tables = jnp.stack([TABLE, jnp.zeros(8, jnp.int32)])
    _, after = paged_kv.decode_step_paged(
        cfg, params, jnp.asarray([5, 0]), jnp.array([32, 0]), pool, tables)
    assert after["s"].dtype == jnp.float32
    # What a bfloat16 state would cost, by the same one-step update: 64
    # steps from a float32 state and from one rounded to bfloat16 after
    # every step.  The rounding compounds where the decay is weak: the
    # state drifts by more than a float32 one moves in float32's noise.
    _, end, _, _, s, _, _ = _scan_and_steps(_cfg(), 0.05, n=64, n_valid=64)
    _, _, _, _, narrow, _, _ = _scan_and_steps(_cfg(), 0.05, n=64,
                                               n_valid=64,
                                               at_rest=jnp.bfloat16)
    assert np.abs(narrow - s).max() > 100 * np.abs(end - s).max()
    assert np.abs(narrow - s).max() > 1e-3


# (7) what the family refuses ----------------------------------------------------------

TIER = dict(name="nano", model_preset="hybrid_kda_test", decode_batch=2,
            max_new_tokens=8, kv_block_size=BLOCK,
            prefill_buckets=(32, 64, 128, 256), prefill_chunk_tokens=16,
            decode_steps_per_tick=2, enable_prefix_cache=False)


@pytest.mark.parametrize("what,kw", [
    ("kv_quantize", dict(kv_quantize="int8")),
    ("draft_preset", dict(draft_preset="draft_test")),
    ("host_kv_bytes", dict(host_kv_bytes=1 << 20)),
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("prefill_chunk_tokens", dict(prefill_chunk_tokens=0)),
])
@pytest.mark.parametrize("preset", ["hybrid_kda_test", "hybrid_cca_test"])
def test_unsupported_combinations_raise_by_the_familys_one_row(preset, what,
                                                               kw):
    # One test for the two patterns that came after the family's row was
    # written (tests/test_hybrid_cca.py's cases are these): neither added
    # a row of its own.
    with pytest.raises(ValueError, match="state-space hybrid family"):
        ContinuousBatchingEngine(TierConfig(**{
            **TIER, "model_preset": preset, **kw}), seed=0)


def test_a_mesh_is_refused_and_the_block_programs_refuse_the_pool():
    from distributed_llm_tpu.parallel.mesh import tp_mesh
    with pytest.raises(ValueError, match="tensor-parallel mesh"):
        ContinuousBatchingEngine(TierConfig(**TIER), seed=0,
                                 mesh=tp_mesh(jax.devices(), 2))
    pool = _pool(_cfg())
    for program in (lambda: paged_kv.copy_block(pool, 1, 2),
                    lambda: paged_kv.gather_blocks(pool, jnp.array([1]))):
        with pytest.raises(NotImplementedError, match="recurrent row"):
            program()
    with pytest.raises(NotImplementedError, match="cold prefill"):
        models.serving_prefill(_cfg(), None, None, None)


# the engine --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    MODEL_PRESETS["hybrid_kda_test_f32"] = _cfg(name="hybrid_kda_test_f32")
    eng = ContinuousBatchingEngine(TierConfig(**{
        **TIER, "model_preset": "hybrid_kda_test_f32"}), seed=SEED)
    yield eng
    eng.stop()
    del MODEL_PRESETS["hybrid_kda_test_f32"]


def test_tick_nests_two_whiles_and_the_chunk_program_one(engine,
                                                         monkeypatch):
    # The MODEL's loops: on this CPU the grouped product's kernel is
    # interpreted, its loop over the touched groups an HLO ``while`` of
    # its own (tests/test_hybrid_ssm.py, the same test).
    from distributed_llm_tpu.ops import grouped_product
    monkeypatch.setattr(grouped_product, "serves", lambda *a: False)
    cfg = engine.cfg
    assert cfg.family == "hybrid" and cfg.layer_period == "KEKELEKE"
    assert cfg.layer_lead == "K-"

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tick = engine._decode_step().lower(
        engine.params, engine.pool, i32(2, 2), i32(2), i32(2),
        jax.ShapeDtypeStruct((2,), jnp.float32), key).compile().as_text()
    # Steps of a tick, periods of a step — the lead sublayers inline
    # ahead of the periods' scan, which stays a loop of TWO repetitions
    # (one would be unrolled, and the tick would nest one ``while``).
    assert _while_depth(tick) == 2
    chunk = engine._chunk_prefill_fn(16, 256).lower(
        engine.params, engine.pool, i32(1, 16), i32(1), i32(1), i32(16),
        key, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    assert _while_depth(chunk) == 1
    # The traced programs keep the scopes the per-layer metrics read.
    for scope in ("kda_proj", "kda_conv", "kda_gate", "kda_out_norm",
                  "latent_attention", "kv_write", "moe_router",
                  "moe_experts", "shared_expert", "ffn"):
        assert scope in chunk and scope in tick, scope
    assert "kda_scan" in chunk and "kda_step" in tick


def test_engine_generates_the_references_greedy_tokens(engine, ref):
    prompt = "a matrix a head and three conv tails"
    out = engine.generate(prompt, max_new_tokens=6)
    ids = [engine.tokenizer.bos_id] + list(prompt.encode())
    seq = np.asarray(ids + list(out.token_ids[:6]), np.int32)
    full = _reference(ref, TINY, seq, len(ids), seed=SEED)
    # Greedy: each generated id is the reference's largest logit at the
    # position before it, or within float32's noise of it.
    for i, tok in enumerate(out.token_ids[:6]):
        row = full[i]
        assert row[tok] >= row.max() - 1e-4, (i, tok, int(row.argmax()))


def test_stats_name_the_rows_their_bytes_and_the_latent_rows_beside_them(
        engine):
    engine.generate("x" * 40, max_new_tokens=2)
    state = engine.state_stats()
    assert state["mixer"] == "kda" and state["layers"] == 7
    assert state["rows"] == 2 and state["resets_total"] >= 1
    # A row: 7 layers x (4 heads x 16 x 16 float32 + 3 taps x 192
    # channels; the roofline counts 2 bytes a number but for "float32").
    assert state["row_bytes"] == 7 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert state["kv_layers"] == 2
    assert state["kv_bytes_per_token"] == 2 * 32 * 2
    assert engine.decode_attention_form() == "latent"
    moe = engine.moe_stats()
    assert len(moe["expert_tokens"]["decode"]) == 8
    assert len(moe["expert_tokens"]["decode"][0]) == 4
    # Half the router's outputs are absent: some assignments went there.
    assert moe["absent_assignments"]["decode"] > 0


# configuration, pool, roofline, int8 --------------------------------------------------

def test_the_pool_holds_latent_rows_a_matrix_state_and_three_tails():
    cfg = _cfg()
    pool = _pool(cfg)
    assert cfg.cache_row_width == 32 and cfg.kv_layers == 2
    # The latent row rests at whole lane-widths (ISSUE 57).
    assert cfg.cache_row_rest_width == 128
    assert pool["c"].shape == (2, 17, BLOCK, 128)
    assert pool["s"].shape == (7, 2, 4, 16, 16)
    assert pool["t"].shape == (7, 2, 3, 192)
    assert list(pool) == ["c", "s", "t", "owner"]


def test_the_pattern_is_one_family_and_its_checks_say_what_each_needs():
    cfg = _cfg()
    assert cfg.family == "hybrid" and cfg.hybrid and not cfg.latent
    assert [cfg.layers_of(k) for k in "KLE-M*C"] == [7, 2, 8, 1, 0, 0, 0]
    assert cfg.layer_segments == (("K", 1), ("-", 1), ("KEKELEKE", 2))
    assert hybrid_ssm.kind_index(cfg, "K") == ([0, 1, 1, 2, 2, 2, 2, 3], 3)
    assert hybrid_ssm.kind_index(cfg, "L") == ([0, 0, 0, 0, 0, 1, 1, 1], 1)
    # "L" rotates where the pattern states rotary (ISSUE 59:
    # tests/test_hybrid_latent_rotary.py); "*" still applies none.
    hybrid_ssm.check(dataclasses.replace(cfg, rotary=True))
    with pytest.raises(ValueError, match="rotary False"):
        hybrid_ssm.check(dataclasses.replace(
            MODEL_PRESETS["hybrid_test"], rotary=True))
    for mixed, n in (("KEM", 3), ("K-LE*E", 6)):
        with pytest.raises(ValueError, match="beside no"):
            hybrid_ssm.check(dataclasses.replace(
                cfg, layer_pattern=mixed, num_layers=n))
    with pytest.raises(ValueError, match="only a pattern with 'L'"):
        hybrid_ssm.check(dataclasses.replace(cfg, kv_lora_rank=0))
    with pytest.raises(ValueError, match="q_lora_rank 0"):
        hybrid_ssm.check(dataclasses.replace(cfg, q_lora_rank=8))
    # The other patterns' sides of each branch: no lead, no latent row.
    for name in ("hybrid_test", "hybrid_mamba1_test", "hybrid_cca_test"):
        other = MODEL_PRESETS[name]
        hybrid_ssm.check(other)
        assert other.layer_lead == "" and not other.kv_lora_rank
    assert "lead" not in _params_shapes(MODEL_PRESETS["hybrid_test"])
    assert len(_params_shapes(cfg)["lead"]) == 2


def _params_shapes(cfg):
    return jax.eval_shape(lambda: models.init_params(cfg, 0))


# What each preset and each benchmark configuration resolved to before a
# pattern could state ``kv_lora_rank`` (PR 54 moved ``layer_pattern`` ahead
# of it in ``ModelConfig.family``).
PRESET_FAMILIES = {
    "latent_test": "latent", "hybrid_test": "hybrid",
    "hybrid_mamba1_test": "hybrid", "hybrid_cca_test": "hybrid",
    "hybrid_kda_test": "hybrid", "shared_kv_test": "shared_kv"}
CONFIG_FAMILIES = {
    "smollm2-1.7b": "dense", "xing4.0-29b-a4b": "latent",
    "nemotron-3-nano-30b-a3b": "hybrid",
    "phi-4-mini-flash-reasoning": "shared_kv", "jamba2-3b": "hybrid",
    "zaya1-8b": "hybrid", "kimi-linear-48b-a3b": "hybrid",
    "sarvam-105b": "hybrid"}


def test_every_preset_resolves_to_the_family_it_did():
    for name, cfg in MODEL_PRESETS.items():
        # (A module's engine fixture registers its preset again in
        # float32, under the name + "_f32".)
        assert cfg.family == PRESET_FAMILIES.get(
            name.removesuffix("_f32"), "dense"), name
        assert cfg.latent == (cfg.family == "latent")
        assert cfg.hybrid == (cfg.family in ("hybrid", "shared_kv"))
        if cfg.family != "hybrid":
            assert cfg.layer_lead == ""


def _bench_configs():
    return sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "configs", "*.json")))


@pytest.mark.parametrize("name", list(CONFIG_FAMILIES))
def test_every_benchmark_configuration_resolves_to_the_family_it_did(
        name, monkeypatch):
    assert _bench_configs() == sorted(CONFIG_FAMILIES)
    bench = os.path.join(ROOT, "benchmark")
    monkeypatch.syspath_prepend(bench)
    spec = importlib.util.spec_from_file_location(
        "_benchmark_cluster", os.path.join(bench, "cluster.py"))
    cluster = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cluster)
    with open(os.path.join(bench, "configs", name + ".json")) as f:
        entries = cluster.tier_entries(json.load(f), False)
    cfg = cluster.program_config(entries["nano"])
    assert cfg.family == CONFIG_FAMILIES[name]
    assert cfg.kv_layers == {
        "dense": cfg.num_layers, "latent": cfg.num_layers,
        "shared_kv": 1}.get(cfg.family, sum(
            cfg.layers_of(kind) for kind in "*CL"))
    sys.modules.pop("manifest", None)


def test_int8_weights_reach_the_patterns_matrices():
    from distributed_llm_tpu.ops.quant import is_quantized, quantize_params
    cfg = _cfg()
    q = jax.jit(quantize_params)(_params(cfg))
    assert is_quantized(q["embed"]) and is_quantized(q["head"])
    lead_kda, lead_mlp = q["lead"]
    kda, exp, latent = q["periods"][0], q["periods"][1], q["periods"][4]
    for lp in (lead_kda, kda):
        assert all(is_quantized(lp[k]) for k in ("wq", "wk", "wv", "wo"))
        assert not any(is_quantized(v) for k, v in lp.items()
                       if k.startswith(("conv", "w_f", "w_g", "w_beta",
                                        "a_log", "dt_bias", "gn")))
    assert all(is_quantized(lead_mlp[k])
               for k in ("w_gate", "w_up", "w_down"))
    assert all(is_quantized(latent[k])
               for k in ("wq", "w_kva", "w_kvb", "wo"))
    assert all(is_quantized(exp[k]) for k in hybrid_ssm.EXPERT_KEYS
               + ("ws_gate", "ws_up", "ws_down"))
    assert not is_quantized(exp["router"])
    # And the quantized tree serves: a layer at a time, XLA's product.
    last, _ = _prefill(cfg, q, _pool(cfg), TOKENS[:20])
    assert np.isfinite(np.asarray(last)).all()


def test_roofline_counts_the_patterns_matrices_rows_and_latent_rows():
    from distributed_llm_tpu.utils import roofline
    cfg = _cfg("bfloat16")
    h = 64
    kda = 4 * h * 64 + 2 * 16 * (h + 64) + h * 4 + 4 * 3 * 64
    latent = h * 4 * 24 + h * 32 + 24 * 4 * 32 + 4 * 16 * h
    fixed = h * 8 + 3 * h * 32                  # router, gated shared
    expert, mlp = 3 * h * 32, 3 * h * 96
    # Of a token's 3 choices the held half computes 1.5 at uniform
    # routing.
    assert roofline.active_matmul_params(cfg) == (
        7 * kda + 2 * latent + 8 * fixed + 12 * expert + mlp + 512 * h)
    # Every held expert, embedding and head, a gain a sublayer and the
    # final one.
    assert roofline.weight_bytes(cfg) == (
        7 * kda + 2 * latent + 8 * (fixed + 4 * expert) + mlp
        + 2 * 512 * h + 19 * h) * 2
    assert roofline.kv_bytes_per_pos(cfg) == 2 * 32 * 2
    assert roofline.state_row_bytes(cfg) == 7 * (4 * 16 * 16 * 4
                                                 + 3 * 192 * 2)
