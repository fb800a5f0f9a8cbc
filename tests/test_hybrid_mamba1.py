"""The hybrid family's other pattern (models/hybrid_ssm.py: Mamba-1 rows
with inner norms beside paged attention layers of ONE K/V head, a dense
gated MLP after every mixer, a tied head: AI21's Jamba block at
``num_experts`` 1) against its plain float32 reference
(benchmark/reference/ssm_attention_mlp_decoder.py, which imports nothing
of the program), at the tiny ``hybrid_mamba1_test`` preset: two periods of
"M-M-*-M-", so both mixers and the scan's repeat are present.

(1) system against reference on logits, prefill in several chunks then
decode through the cache; (2) what a wrong block does to that tolerance (a
dropped inner norm, a dropped gain, a state kept in bfloat16); (3) one K/V
head across block boundaries and window rungs, chunk form and tick form;
(4) rows that are not valid; (5) the engine, its programs, /stats and the
counter of chunks by rung; (6) configuration, pool, roofline.
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
from distributed_llm_tpu.models import hybrid_ssm, shared_kv_hybrid
from distributed_llm_tpu.models import transformer
from test_latent_moe import _while_depth

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The keys of the tiny preset, as the reference reads them.
TINY = {
    "hidden_size": 64, "num_hidden_layers": 8, "attn_layer_period": 4,
    "attn_layer_offset": 2, "vocab_size": 512, "intermediate_size": 96,
    "num_attention_heads": 2, "num_key_value_heads": 1, "mamba_expand": 2,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 4,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "rms_norm_eps": 1e-6, "torch_dtype": "float32",
}
SEED = 5
BLOCK = 16
TABLE = jnp.arange(1, 9, dtype=jnp.int32)
# 100 ids: 72 of prompt (chunks of 16: four whole and 8 + 8 of padding)
# and 28 decode steps: the K/V cross six block boundaries, the state every
# chunk edge.
TOKENS = np.random.default_rng(0).integers(0, 500, 100).astype(np.int32)
N_PROMPT = 72
INNER_NORMS = ("dt_ln", "b_ln", "c_ln")
# Float32 against float32: what the two orders of operations leave.
F32_TOL = 2e-5


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "ssm_attention_mlp_reference", os.path.join(
            ROOT, "benchmark", "reference", "ssm_attention_mlp_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(dtype="float32", **kw):
    return dataclasses.replace(MODEL_PRESETS["hybrid_mamba1_test"],
                               dtype=dtype, **kw)


def _params(cfg, seed=SEED):
    return jax.jit(lambda s: models.init_params(cfg, s))(jnp.int32(seed))


def _pool(cfg, slots=2):
    return paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=BLOCK, max_slots=slots, max_seq_len=128))


def _prefill(cfg, params, pool, tok, table=TABLE, chunk=16, pad=0,
             windows=(128,), between=None):
    """``tok`` through the chunk program, the last chunk right-padded
    with ``pad``, each chunk at the smallest of ``windows`` that holds its
    end; ``between`` may change the pool from chunk to chunk.  Returns
    (the last valid position's hidden, pool)."""
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
        if between is not None:
            pool = between(pool)
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


def _mamba_layers(params, change):
    """``params`` with ``change`` applied to every Mamba-1 layer's
    weights."""
    return {**params, "periods": [change(lp) if "w_x" in lp else lp
                                  for lp in params["periods"]]}


@pytest.fixture(scope="module")
def want(ref):
    return _reference(ref)


# (1) against the reference ------------------------------------------------------

def test_float32_chunks_then_decode_match_the_reference(want):
    cfg = _cfg()
    params = _params(cfg)
    assert "head" not in params                         # tied
    got = _serve(cfg, params)
    assert got.shape == want.shape == (len(TOKENS) - N_PROMPT + 1, 512)
    assert _rel(got, want) < F32_TOL


def test_bfloat16_chunks_then_decode_stay_within_its_rounding(ref):
    cfg = _cfg("bfloat16")
    got = _serve(cfg, _params(cfg))
    want = _reference(ref, {**TINY, "torch_dtype": "bfloat16"})
    # bfloat16 weights on both sides; the system also rounds activations
    # (2^-9 a rounding through 16 sublayers), the reference none.
    assert 1e-3 < _rel(got, want) < 3e-2


# (2) what a wrong block does to the float32 tolerance ---------------------------

def test_the_inner_norms_gains_are_drawn_away_from_one():
    lp = _params(_cfg())["periods"][0]
    for key, width in zip(INNER_NORMS, (4, 8, 8)):
        g = np.asarray(lp[key])
        assert g.shape == (2, width) and np.abs(g - 1).max() > 0.02


@pytest.mark.parametrize("control", [
    "no inner norms", "delta's norm without its gain",
    "B's norm without its gain", "C's norm without its gain"])
def test_a_dropped_inner_norm_fails_the_float32_tolerance(control, want):
    cfg = _cfg()
    params = _params(cfg)
    if control == "no inner norms":
        # A tree without the gains is the shared-K/V family's mixer:
        # ``_time_step`` runs no norm.
        wrong = _mamba_layers(params, lambda lp: {
            k: v for k, v in lp.items() if k not in INNER_NORMS})
    else:
        key = {"d": "dt_ln", "B": "b_ln", "C": "c_ln"}[control[0]]
        wrong = _mamba_layers(params, lambda lp: {
            **lp, key: jnp.ones_like(lp[key])})
    # A gain a tenth off 1 on one of the three small vectors moves the
    # logits 4 to 24 times the tolerance; no norm at all 200 times.
    least = 100 if control == "no inner norms" else 3
    assert _rel(_serve(cfg, wrong), want) > least * F32_TOL


def test_a_state_rounded_to_bfloat16_at_rest_fails_that_tolerance():
    # The control for the state's precision is held HERE, on the state
    # itself: at this preset's weights a state rounded after each of the
    # prompt's chunks moves the logits by 5e-6, under float32's own noise.
    cfg = _cfg()
    lp = hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(SEED), "M")
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(32, cfg.ssm_inner)), jnp.float32)
    tail0 = jnp.zeros((cfg.ssm_conv - 1, cfg.ssm_inner), jnp.float32)
    state0 = jnp.asarray(rng.normal(size=(cfg.ssm_state, cfg.ssm_inner)),
                         jnp.float32)
    whole = hybrid_ssm.mamba1_scan(cfg, lp, a, state0, tail0, jnp.int32(32))

    def halves(rest):
        m1, s, t = hybrid_ssm.mamba1_scan(cfg, lp, a[:16], state0, tail0,
                                          jnp.int32(16))
        m2, s, t = hybrid_ssm.mamba1_scan(cfg, lp, a[16:], rest(s), t,
                                          jnp.int32(16))
        return jnp.concatenate([m1, m2]), s
    m, s = halves(lambda s: s)
    # Two chunks are the one scan cut in two: bit for bit.
    np.testing.assert_array_equal(np.asarray(m), np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(whole[1]))
    m, s = halves(lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    # bfloat16 at rest: 2^-9 of states of size 1 is 2e-3, a hundred times
    # the 2e-5 of the tests above; the inner norms make C of size 1, so
    # the output moves as much.
    assert np.abs(np.asarray(s) - np.asarray(whole[1])).max() > 1e-3
    assert np.abs(np.asarray(m) - np.asarray(whole[0])).max() > 1e-3


# (3) one K/V head: block boundaries, window rungs, both forms -------------------

def test_one_kv_head_rows_are_one_head_wide_in_the_pool():
    cfg = _cfg()
    pool = _pool(cfg)
    assert cfg.cache_row_width == cfg.head_dim == 32 and cfg.kv_layers == 2
    assert pool["k"].shape == pool["v"].shape == (2, 17, BLOCK, 32)
    assert pool["s"].shape == (6, 2, 8, 128)            # [state, inner]
    assert pool["t"].shape == (6, 2, 3, 128)
    assert set(pool) == {"k", "v", "s", "t", "owner"}


def test_chunks_on_two_window_rungs_equal_chunks_on_the_whole_span(want):
    cfg = _cfg()
    params = _params(cfg)
    # Chunks ending at 16 and 32 attend a window of 32 (two blocks), the
    # rest the span: what a narrower gather leaves out is masked anyway.
    got = _serve(cfg, params, windows=(32, 128))
    assert _rel(got, want) < F32_TOL
    np.testing.assert_allclose(got, _serve(cfg, params), rtol=0, atol=2e-5)


def test_the_tick_on_a_cut_table_equals_the_tick_on_the_whole_row():
    cfg = _cfg()
    params = _params(cfg)
    _, pool = _prefill(cfg, params, _pool(cfg), TOKENS[:40])
    tables = jnp.stack([jnp.zeros(8, jnp.int32), TABLE])
    cur, pos = jnp.asarray([0, 7]), jnp.array([0, 40])
    whole, _ = paged_kv.decode_step_paged(cfg, params, cur, pos, pool, tables)
    # Position 40 sits in the third block: a rung of 48 positions holds it.
    cut, _ = paged_kv.decode_step_paged(cfg, params, cur, pos, pool,
                                        tables[:, :3])
    np.testing.assert_allclose(np.asarray(cut[1]), np.asarray(whole[1]),
                               rtol=0, atol=2e-5)


# (4) rows that are not valid ------------------------------------------------------

def test_padding_and_an_idle_slot_leave_a_rows_state_bit_identical():
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:40]
    _, a = _prefill(cfg, params, _pool(cfg), tok, pad=0)
    _, b = _prefill(cfg, params, _pool(cfg), tok, pad=77)
    # What the 8 padded positions of the last chunk hold never reaches
    # the state (their time step is 0) or the tail (it stops at the last
    # valid row).  Bit for bit.
    for key in ("s", "t", "owner"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))
    assert np.asarray(a["owner"]).tolist() == [1, 0]

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
    # not valid and keeps state and tail, the second's moves.
    for key in ("s", "t"):
        np.testing.assert_array_equal(np.asarray(after[key][:, 0]),
                                      np.asarray(pool[key][:, 0]))
        assert not np.array_equal(np.asarray(after[key][:, 1]),
                                  np.asarray(pool[key][:, 1]))


def test_a_sequence_admitted_into_a_used_row_starts_from_zero_state():
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:32]
    clean, _ = _prefill(cfg, params, _pool(cfg), tok)
    _, used = _prefill(cfg, params, _pool(cfg), TOKENS[40:72])
    again, _ = _prefill(cfg, params, used, tok)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(clean))


# (5) the engine ---------------------------------------------------------------------

TIER = dict(name="nano", model_preset="hybrid_mamba1_test", decode_batch=2,
            max_new_tokens=8, kv_block_size=BLOCK,
            prefill_buckets=(32, 64, 128, 256), prefill_chunk_tokens=16,
            decode_steps_per_tick=2, enable_prefix_cache=False)


@pytest.fixture(scope="module")
def engine():
    MODEL_PRESETS["hybrid_mamba1_test_f32"] = _cfg(
        name="hybrid_mamba1_test_f32")
    eng = ContinuousBatchingEngine(TierConfig(**{
        **TIER, "model_preset": "hybrid_mamba1_test_f32"}), seed=SEED)
    yield eng
    eng.stop()
    del MODEL_PRESETS["hybrid_mamba1_test_f32"]


def test_tick_nests_two_whiles_and_the_chunk_program_one(engine):
    cfg = engine.cfg
    assert cfg.family == "hybrid" and cfg.layer_period == "M-M-*-M-"

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tick = engine._decode_step().lower(
        engine.params, engine.pool, i32(2, 2), i32(2), i32(2),
        jax.ShapeDtypeStruct((2,), jnp.float32), key).compile().as_text()
    # Steps of a tick, periods of a step — and nothing inside a period.
    assert _while_depth(tick) == 2
    chunk = engine._chunk_prefill_fn(16, 256).lower(
        engine.params, engine.pool, i32(1, 16), i32(1), i32(1), i32(16),
        key, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    # The chunk's recurrence is the kernel's loop (interpreted here: the
    # kernel's own ``while``s are not the model's): one loop over periods.
    assert _while_depth(chunk) >= 1
    # The traced programs keep the scopes the per-layer metrics read.
    for scope, text in (("ssm_conv", chunk), ("ssm_scan", chunk),
                        ("ssm_inner_norms", chunk), ("kv_write", chunk),
                        ("attention", chunk), ("ffn", chunk),
                        ("ssm_in_proj", chunk), ("ssm_step", tick),
                        ("ssm_inner_norms", tick), ("ffn", tick)):
        assert scope in text, scope


def test_engine_generates_the_references_greedy_tokens(engine, ref):
    prompt = "state and attention"
    out = engine.generate(prompt, max_new_tokens=6)
    ids = [engine.tokenizer.bos_id] + list(prompt.encode())
    seq = np.asarray(ids + list(out.token_ids[:6]), np.int32)
    want = _reference(ref, TINY, seq, len(ids), seed=SEED)
    # Greedy: each generated id is the reference's largest logit at the
    # position before it, or within float32's noise of it.
    for i, tok in enumerate(out.token_ids[:6]):
        row = want[i]
        assert row[tok] >= row.max() - 1e-4, (i, tok, int(row.argmax()))


def test_stats_say_the_mixer_its_rows_and_the_kv_beside_them(engine):
    engine.generate("x" * 40, max_new_tokens=2)
    state = engine.state_stats()
    assert state["mixer"] == "mamba1" and state["layers"] == 6
    assert state["rows"] == 2 and state["resets_total"] >= 1
    # A row: 6 layers x (the float32 state 8 x 128 + 3 rows of the conv's
    # input; the roofline counts 2 bytes a number but for "float32").
    assert state["row_bytes"] == 6 * (8 * 128 * 4 + 3 * 128 * 4)
    assert state["kv_layers"] == 2
    assert state["kv_bytes_per_token"] == 2 * 2 * 32 * 2
    assert "ring_layers" not in state


def test_prefill_chunks_are_counted_by_the_window_rung_each_ran_at(engine):
    from distributed_llm_tpu.obs import get_observability
    before = dict(engine.prefill_stats()["chunks_by_window"])
    engine.generate("y" * 40, max_new_tokens=2)     # 41 ids: 3 chunks of 16
    after = engine.prefill_stats()["chunks_by_window"]
    grew = {w: after[w] - before.get(w, 0) for w in after
            if after[w] != before.get(w, 0)}
    # Ends at 16, 32, 48: the smallest rungs of the lane's ladder that
    # hold them.
    ladder = engine._chunk_windows
    want = {}
    for end in (16, 32, 48):
        w = next(w for w in ladder if w >= end)
        want[w] = want.get(w, 0) + 1
    assert grew == want
    text = get_observability().metrics.render()
    assert 'dllm_prefill_chunks_by_window_total{tier="nano",window="' in text


@pytest.mark.parametrize("what,kw", [
    ("kv_quantize", dict(kv_quantize="int8")),
    ("draft_preset", dict(draft_preset="draft_test")),
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("prefill_chunk_tokens", dict(prefill_chunk_tokens=0)),
])
def test_unsupported_combinations_raise_by_family(what, kw):
    with pytest.raises(ValueError, match="state-space hybrid family"):
        ContinuousBatchingEngine(TierConfig(**{**TIER, **kw}), seed=0)


# (6) configuration, pool, roofline -------------------------------------------------

def test_one_copy_of_the_mamba1_mixer_serves_both_row_families():
    assert shared_kv_hybrid._mamba is hybrid_ssm.mamba1
    assert shared_kv_hybrid.ssm_scan is hybrid_ssm.mamba1_scan
    assert shared_kv_hybrid.ssm_step is hybrid_ssm.mamba1_step
    # The shared-K/V family's tree holds no inner norms; this pattern's
    # does.
    skv = shared_kv_hybrid.init_layer(MODEL_PRESETS["shared_kv_test"],
                                      jax.random.PRNGKey(0), "M")
    own = hybrid_ssm.init_layer(_cfg(), jax.random.PRNGKey(0), "M")
    assert not set(INNER_NORMS) & set(skv)
    assert set(INNER_NORMS) <= set(own)
    assert set(own) - set(skv) == set(INNER_NORMS) | {"ln"}


def test_the_pattern_is_one_family_and_its_checks_name_what_is_wrong():
    cfg = _cfg()
    assert cfg.family == "hybrid" and cfg.hybrid and not cfg.shared_kv
    assert [cfg.layers_of(k) for k in "M*-E"] == [6, 2, 8, 0]
    assert cfg.layer_segments == (("M-M-*-M-", 2),)
    assert hybrid_ssm.kind_index(cfg, "M") == ([0, 1, 1, 2, 2, 2, 2, 3], 3)
    with pytest.raises(ValueError, match="ssm_head_dim 1"):
        hybrid_ssm.check(dataclasses.replace(cfg, ssm_head_dim=2,
                                             ssm_heads=64))
    with pytest.raises(ValueError, match="no rotary"):
        hybrid_ssm.check(dataclasses.replace(cfg, rotary=True))
    with pytest.raises(ValueError, match="layer_pattern"):
        hybrid_ssm.check(dataclasses.replace(cfg, layer_pattern="M-M+"))


def test_int8_weights_reach_the_patterns_matrices():
    from distributed_llm_tpu.ops.quant import is_quantized, quantize_params
    q = jax.jit(quantize_params)(_params(_cfg()))
    assert is_quantized(q["embed"]) and "head" not in q
    m, f, _, _, a = q["periods"][:5]
    assert all(is_quantized(m[k]) for k in ("w_in", "w_out"))
    assert all(is_quantized(f[k]) for k in ("w_gate", "w_up", "w_down"))
    assert all(is_quantized(a[k]) for k in ("wq", "wk", "wv", "wo"))
    assert not any(is_quantized(m[k]) for k in
                   ("w_x", "w_dt", "conv_w", "a_log") + INNER_NORMS)


def test_roofline_counts_the_patterns_matrices_state_and_attention_kv():
    from distributed_llm_tpu.utils import roofline
    cfg = _cfg("bfloat16")
    h, di, f = 64, 128, 96
    mamba = h * 2 * di + di * (4 + 16) + 4 * di + di * h
    attn = 2 * h * 64 + 2 * h * 32
    matrices = 6 * mamba + 2 * attn + 8 * 3 * h * f
    assert roofline.active_matmul_params(cfg) == matrices + 512 * h
    # The tied table once, a gain a sublayer and the final one.
    assert roofline.weight_bytes(cfg) == (matrices + 512 * h + 17 * h) * 2
    assert roofline.kv_bytes_per_pos(cfg) == 2 * 2 * 32 * 2
    assert roofline.state_row_bytes(cfg) == 6 * (8 * di * 4 + 3 * di * 2)
