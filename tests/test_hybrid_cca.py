"""The hybrid family's third pattern (models/hybrid_ssm.py: compressed
convolutional attention — paged K/V AND a tail row a slot — then top-1
gated experts under the MLP router with its carry through the depth, the
merge scaled, a tied head: Zyphra's ZAYA1 block) against its plain float32
reference (benchmark/reference/cca_moe_decoder.py, which imports nothing
of the program), at the tiny ``hybrid_cca_test`` preset: three periods of
"CE".

(1) system against reference on logits, prefill in chunks then decode
through the cache, chunk edges at odd and even offsets, the first token;
(2) each named part of the mathematics dropped in turn from the reference
fails that tolerance by a stated multiple; (3) the tail: padding, an idle
slot, a reused slot, bfloat16 at rest; (4) the engine, its programs and
/stats; (5) configuration, pool, roofline, int8.
"""

import dataclasses
import importlib.util
import os

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
    "hidden_size": 64, "num_hidden_layers": 3, "vocab_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"rope_theta": 10000.0}},
    "num_experts": 4, "num_experts_per_tok": 1, "moe_intermediate_size": 32,
    "router_hidden_size": 16, "rms_norm_eps": 1e-5,
    "torch_dtype": "float32",
}
SEED = 5
BLOCK = 16
TABLE = jnp.arange(1, 9, dtype=jnp.int32)
# 100 ids: 72 of prompt and 28 decode steps: the K/V cross six block
# boundaries, the tail every chunk edge and every step.
TOKENS = np.random.default_rng(0).integers(0, 500, 100).astype(np.int32)
N_PROMPT = 72
# Float32 against float32: what the two orders of operations leave.
F32_TOL = 2e-5


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "cca_moe_reference", os.path.join(
            ROOT, "benchmark", "reference", "cca_moe_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load_reference()


def _cfg(dtype="float32", **kw):
    return dataclasses.replace(MODEL_PRESETS["hybrid_cca_test"],
                               dtype=dtype, **kw)


def _params(cfg, seed=SEED):
    return jax.jit(lambda s: models.init_params(cfg, s))(jnp.int32(seed))


def _pool(cfg, slots=2):
    return paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=BLOCK, max_slots=slots, max_seq_len=128))


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
        hidden, pool = jax.jit(
            lambda params, pool, piece, start, total, table, w=window:
            paged_kv.chunk_prefill_paged(cfg, params, piece, start, total,
                                         pool, table, w))(
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

def test_float32_chunks_then_decode_match_the_reference(got, want):
    assert "head" not in _params(_cfg())                # tied
    assert got.shape == want.shape == (len(TOKENS) - N_PROMPT + 1, 512)
    assert _rel(got, want) < F32_TOL


@pytest.mark.parametrize("chunk", [7, 8, 24, 72])
def test_chunk_edges_at_odd_and_even_offsets_give_the_same_numbers(
        chunk, got, want):
    # The tail crosses every edge: 7 cuts the prompt at odd and even
    # positions and pads its last chunk, 72 is the prompt whole.
    cfg = _cfg()
    other = _serve(cfg, _params(cfg), chunk=chunk)
    assert _rel(other, want) < F32_TOL
    np.testing.assert_allclose(other, got, rtol=0, atol=2e-5)


def test_the_first_token_meets_a_zero_row_and_not_itself(ref):
    # x_{-1} = 0: position 0's convolutions and shifted value read zeros.
    # Two ids, one chunk, no decode: logits at positions 0 and 1.
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:2]
    for n in (1, 2):
        last, _ = _prefill(cfg, params, _pool(cfg), tok[:n], chunk=4)
        logits = np.asarray(transformer.logits_from_hidden(params, last))
        full = _reference(ref, TINY, tok[:n], n)
        assert _rel(logits, full[0]) < F32_TOL


def test_bfloat16_chunks_then_decode_stay_within_its_rounding(ref):
    cfg = _cfg("bfloat16")
    out = _serve(cfg, _params(cfg))
    full = _reference(ref, {**TINY, "torch_dtype": "bfloat16"})
    # bfloat16 weights on both sides; the system also rounds activations
    # (2^-9 a rounding through 6 sublayers) and a top-1 choice made by a
    # hair may flip, the reference none.
    assert 1e-3 < _rel(out, full) < 6e-2


# (2) each part of the mathematics, dropped from the reference -------------------

def _without(ref, what, monkeypatch):
    """The reference's logits with one named term left out."""
    model = dict(TINY)
    if what == "the q-k mean":
        monkeypatch.setattr(ref, "qk_mean", lambda model, lat: (0.0, 0.0))
    elif what == "the value shift":
        monkeypatch.setattr(ref, "shifted_values", lambda model, v: v)
    elif what == "tau":
        monkeypatch.setattr(ref, "TAU_MEAN", 1.0)
        monkeypatch.setattr(ref, "TAU_STD", 0.0)
    elif what == "the partial rotary":
        model["partial_rotary_factor"] = 1.0
    elif what == "gamma's carry":
        monkeypatch.setattr(ref, "CARRY_MEAN", 0.0)
        monkeypatch.setattr(ref, "CARRY_STD", 0.0)
    elif what == "the choice-only bias":
        monkeypatch.setattr(ref, "ROUTER_BIAS_STD", 0.0)
    elif what == "the scaled merge":
        monkeypatch.setattr(ref, "RES_GAIN_STD", 0.0)
        monkeypatch.setattr(ref, "RES_BIAS_STD", 0.0)
    return _reference(ref, model)


# The least multiple of the float32 tolerance each dropped term moves the
# logits by; read at this seed: 3719, 5047, 3766, 6862, 282, 174 and
# 57 016 (the embedding is drawn like every matrix, so the token's own row
# does not outweigh what the layers add).  (A bias of 0.01 moves few of 87
# top-1 choices among 4 experts; each moves its token's whole expert.)
DROPPED = {"the q-k mean": 1000, "the value shift": 1500, "tau": 1000,
           "the partial rotary": 2000, "gamma's carry": 80,
           "the choice-only bias": 50, "the scaled merge": 15000}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_a_dropped_term_fails_the_float32_tolerance(what, got, monkeypatch):
    wrong = _without(_load_reference(), what, monkeypatch)
    assert _rel(got, wrong) > DROPPED[what] * F32_TOL


def test_the_terms_a_test_drops_are_drawn_away_from_their_trivial_values():
    attn, exp = _params(_cfg())["periods"]
    assert np.abs(np.asarray(attn["tau"]) - 1).min() > 0.05
    assert np.abs(np.asarray(exp["router_carry"])).min() > 0.1
    assert np.abs(np.asarray(exp["router_bias"])).max() > 1e-3
    a_r, b_r, a_o, b_o = np.moveaxis(np.asarray(attn["res"]), 1, 0)
    assert min(np.abs(a_r - 1).max(), np.abs(a_o - 1).max()) > 0.05
    assert min(np.abs(b_r).max(), np.abs(b_o).max()) > 0.01


def test_the_bias_moves_the_choice_and_never_the_weight():
    cfg = _cfg()
    lp = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["periods"][1])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    carry = jnp.zeros((24, 16), jnp.float32)
    choice, w, r = hybrid_ssm.route_mlp(cfg, lp, x, carry)
    forced = {**lp, "router_bias": jnp.array([0.0, 0.0, 9.0, 0.0])}
    choice2, w2, r2 = hybrid_ssm.route_mlp(cfg, forced, x, carry)
    assert np.asarray(choice2).tolist() == [[2]] * 24
    assert len(set(np.asarray(choice)[:, 0].tolist())) > 1
    # The weight is the chosen expert's probability as it is: under 1,
    # not renormalised to it, and the same number whichever bias chose it.
    p = np.asarray(w2)[:, 0]
    assert (p > 0).all() and (p < 1).all()
    same = np.asarray(choice)[:, 0] == 2
    np.testing.assert_array_equal(np.asarray(w)[same, 0], p[same])
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r2))


# (3) the tail ---------------------------------------------------------------------

def test_padding_and_an_idle_slot_leave_a_rows_tail_bit_identical():
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:40]
    _, a = _prefill(cfg, params, _pool(cfg), tok, pad=0)
    _, b = _prefill(cfg, params, _pool(cfg), tok, pad=77)
    # What the 8 padded positions of the last chunk hold never reaches
    # the tail: it is the last VALID position's row.  Bit for bit.
    for key in ("t", "owner"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))
    assert np.asarray(a["owner"]).tolist() == [1, 0]
    assert np.abs(np.asarray(a["t"][:, 0])).min() > 0

    other = jnp.arange(9, 17, dtype=jnp.int32)
    pool = paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=BLOCK, max_slots=2, max_seq_len=128, pool_blocks=16))
    _, pool = _prefill(cfg, params, pool, tok)
    _, pool = _prefill(cfg, params, pool, tok[:20][::-1].copy(), table=other)
    assert np.asarray(pool["owner"]).tolist() == [1, 9]
    tables = jnp.stack([jnp.zeros(8, jnp.int32), other])
    after = pool
    for p in range(20, 24):
        _, after = paged_kv.decode_step_paged(
            cfg, params, jnp.asarray([5, 6]), jnp.array([p, p]), after,
            tables)
    # The first sequence's table is all trash in these steps: its row is
    # not valid and keeps its tail, the second's moves.
    np.testing.assert_array_equal(np.asarray(after["t"][:, 0]),
                                  np.asarray(pool["t"][:, 0]))
    assert not np.array_equal(np.asarray(after["t"][:, 1]),
                              np.asarray(pool["t"][:, 1]))


def test_a_sequence_admitted_into_a_used_row_starts_from_a_zero_tail():
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:32]
    clean, _ = _prefill(cfg, params, _pool(cfg), tok)
    _, used = _prefill(cfg, params, _pool(cfg), TOKENS[40:72])
    again, _ = _prefill(cfg, params, used, tok)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(clean))


def test_a_bfloat16_tail_at_rest_costs_nothing_the_activations_do_not():
    # The configuration states the tail in the model's dtype.  What it
    # holds — the latents, the first convolution's output, the shifted
    # value — are rounded to that dtype where they are MADE, before
    # anything reads them, so a row at rest is exactly what an unbroken
    # pass would have read: cutting a bfloat16 sequence anywhere changes
    # no number of the convolutions.
    cfg = _cfg("bfloat16")
    lp = hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(SEED), "C")
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(size=(1, 32, 96)), jnp.bfloat16)
    v2 = jnp.asarray(rng.normal(size=(1, 32, 16)), jnp.bfloat16)
    zero = jnp.zeros((1, cfg.cca_tail_width), jnp.bfloat16)
    assert cfg.cca_tail_width == 2 * 96 + 16
    whole = hybrid_ssm.cca_conv(cfg, lp, u, v2, zero)
    for cut in (1, 15, 16):
        a = hybrid_ssm.cca_conv(cfg, lp, u[:, :cut], v2[:, :cut], zero)
        assert a[2].dtype == jnp.bfloat16
        b = hybrid_ssm.cca_conv(cfg, lp, u[:, cut:], v2[:, cut:],
                                a[2][:, cut])
        for i in range(3):
            np.testing.assert_array_equal(
                np.asarray(jnp.concatenate([a[i], b[i][:, i // 2:]], 1),
                           np.float32), np.asarray(whole[i], np.float32))
    # What a NARROWER row would cost is seen by the same cut: a tail kept
    # in float8 moves the second half's convolutions by its rounding.
    a = hybrid_ssm.cca_conv(cfg, lp, u[:, :16], v2[:, :16], zero)
    narrow = a[2][:, 16].astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    b = hybrid_ssm.cca_conv(cfg, lp, u[:, 16:], v2[:, 16:], narrow)
    assert np.abs(np.asarray(b[0][:, 0] - whole[0][:, 16])).max() > 1e-2


# (4) the engine ---------------------------------------------------------------------

TIER = dict(name="nano", model_preset="hybrid_cca_test", decode_batch=2,
            max_new_tokens=8, kv_block_size=BLOCK,
            prefill_buckets=(32, 64, 128, 256), prefill_chunk_tokens=16,
            decode_steps_per_tick=2, enable_prefix_cache=False)


@pytest.fixture(scope="module")
def engine():
    MODEL_PRESETS["hybrid_cca_test_f32"] = _cfg(name="hybrid_cca_test_f32")
    eng = ContinuousBatchingEngine(TierConfig(**{
        **TIER, "model_preset": "hybrid_cca_test_f32"}), seed=SEED)
    yield eng
    eng.stop()
    del MODEL_PRESETS["hybrid_cca_test_f32"]


def test_tick_nests_two_whiles_and_the_chunk_program_one(engine,
                                                         monkeypatch):
    # The MODEL's loops: on this CPU the grouped product's kernel is
    # interpreted, its loop over the touched groups an HLO ``while`` of
    # its own (tests/test_hybrid_ssm.py, the same test).
    from distributed_llm_tpu.ops import grouped_product
    monkeypatch.setattr(grouped_product, "serves", lambda *a: False)
    cfg = engine.cfg
    assert cfg.family == "hybrid" and cfg.layer_period == "CE"

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tick = engine._decode_step().lower(
        engine.params, engine.pool, i32(2, 2), i32(2), i32(2),
        jax.ShapeDtypeStruct((2,), jnp.float32), key).compile().as_text()
    # Steps of a tick, periods of a step — and nothing inside a period:
    # ONE layer loop, the router's state a second carry of it.
    assert _while_depth(tick) == 2
    chunk = engine._chunk_prefill_fn(16, 256).lower(
        engine.params, engine.pool, i32(1, 16), i32(1), i32(1), i32(16),
        key, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    assert _while_depth(chunk) == 1
    # The traced programs keep the scopes the per-layer metrics read.
    for scope in ("cca_proj", "cca_conv", "cca_qk_norm", "kv_write",
                  "attention", "moe_router", "moe_experts"):
        assert scope in chunk and scope in tick, scope


def test_engine_generates_the_references_greedy_tokens(engine, ref):
    prompt = "a conv tail and a shifted value"
    out = engine.generate(prompt, max_new_tokens=6)
    ids = [engine.tokenizer.bos_id] + list(prompt.encode())
    seq = np.asarray(ids + list(out.token_ids[:6]), np.int32)
    full = _reference(ref, TINY, seq, len(ids), seed=SEED)
    # Greedy: each generated id is the reference's largest logit at the
    # position before it, or within float32's noise of it.
    for i, tok in enumerate(out.token_ids[:6]):
        row = full[i]
        assert row[tok] >= row.max() - 1e-4, (i, tok, int(row.argmax()))


def test_stats_name_the_tail_rows_their_bytes_and_the_kv_beside_them(engine):
    engine.generate("x" * 40, max_new_tokens=2)
    state = engine.state_stats()
    assert state["mixer"] == "cca_tail" and state["layers"] == 3
    assert state["rows"] == 2 and state["resets_total"] >= 1
    # A row: 3 layers x (2 x 96 conv inputs + 16 shifted values); the
    # roofline counts 2 bytes a number but for "float32".
    assert state["row_bytes"] == 3 * 208 * 4
    assert state["kv_layers"] == 3
    assert state["kv_bytes_per_token"] == 2 * 3 * 2 * 16 * 2
    moe = engine.moe_stats()
    assert len(moe["expert_tokens"]["decode"]) == 3
    assert len(moe["expert_tokens"]["decode"][0]) == 4
    assert moe["absent_assignments"] == {"decode": 0, "prefill": 0}


# What the family refuses for this pattern, by its one row of
# ``_FAMILY_REFUSALS``: tests/test_hybrid_kda.py
# ``test_unsupported_combinations_raise_by_the_familys_one_row``, one
# parametrised test over this pattern's preset and that file's.


# (5) configuration, pool, roofline, int8 -----------------------------------------

def test_the_pool_holds_kv_layers_tail_rows_and_an_empty_state():
    cfg = _cfg()
    pool = _pool(cfg)
    assert cfg.cache_row_width == 32 and cfg.kv_layers == 3
    assert pool["k"].shape == pool["v"].shape == (3, 17, BLOCK, 32)
    assert pool["t"].shape == (3, 2, 1, 208)
    assert pool["s"].size == 0 and pool["s"].shape[:2] == (0, 2)
    assert set(pool) == {"k", "v", "s", "t", "owner"}


def test_the_pattern_is_one_family_and_its_checks_say_what_each_needs():
    cfg = _cfg()
    assert cfg.family == "hybrid" and cfg.hybrid and not cfg.shared_kv
    assert [cfg.layers_of(k) for k in "C*ME-"] == [3, 0, 0, 3, 0]
    assert cfg.layer_segments == (("CE", 3),)
    assert hybrid_ssm.kind_index(cfg, "C") == ([0, 1], 1)
    with pytest.raises(ValueError, match="'C' states rotary True"):
        hybrid_ssm.check(dataclasses.replace(cfg, rotary=False))
    with pytest.raises(ValueError, match="even number of K/V heads"):
        hybrid_ssm.check(dataclasses.replace(cfg, num_kv_heads=1))
    for mixed in ("CEM", "CE*"):
        with pytest.raises(ValueError, match="has no 'M' and no"):
            hybrid_ssm.check(dataclasses.replace(
                cfg, layer_pattern=mixed, num_layers=3))
    with pytest.raises(ValueError, match="'relu2'.*or 'swiglu'"):
        hybrid_ssm.check(dataclasses.replace(cfg, expert_act="gelu"))
    # The other two patterns' sides of each branch.
    for name in ("hybrid_test", "hybrid_mamba1_test"):
        other = MODEL_PRESETS[name]
        hybrid_ssm.check(other)
        assert not other.router_hidden and not other.rotary
    assert MODEL_PRESETS["hybrid_test"].expert_act == "relu2"


def test_int8_weights_reach_the_patterns_matrices():
    from distributed_llm_tpu.ops.quant import is_quantized, quantize_params
    cfg = _cfg()
    q = jax.jit(quantize_params)(_params(cfg))
    attn, exp = q["periods"]
    assert is_quantized(q["embed"]) and "head" not in q
    assert all(is_quantized(attn[k]) for k in ("wq", "wk", "wv", "wo"))
    assert all(is_quantized(exp[k]) for k in hybrid_ssm.EXPERT_KEYS)
    assert not any(is_quantized(v) for k, v in {**attn, **exp}.items()
                   if k.startswith(("conv", "router", "tau", "res")))
    # And the quantized tree serves: a layer at a time, XLA's product.
    last, _ = _prefill(cfg, q, _pool(cfg), TOKENS[:20])
    assert np.isfinite(np.asarray(last)).all()


def test_roofline_counts_the_patterns_matrices_tail_and_kv():
    from distributed_llm_tpu.utils import roofline
    cfg = _cfg("bfloat16")
    h = 64
    attn = 2 * h * 64 + 2 * h * 32 + 2 * 96 + 2 * 6 * 16 * 16
    router = h * 16 + 2 * 16 * 16 + 16 * 4
    expert = 3 * h * 32
    assert roofline.active_matmul_params(cfg) == (
        3 * (attn + router + expert) + 512 * h)
    # Every held expert, the tied table once, a gain a sublayer and the
    # final one.
    assert roofline.weight_bytes(cfg) == (
        3 * (attn + router + 4 * expert) + 512 * h + 7 * h) * 2
    assert roofline.kv_bytes_per_pos(cfg) == 2 * 3 * 32 * 2
    assert roofline.state_row_bytes(cfg) == 3 * 208 * 2
