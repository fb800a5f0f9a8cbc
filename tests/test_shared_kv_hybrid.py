"""The state-space / window-attention / shared-K/V family
(models/shared_kv_hybrid.py) against its plain float32 reference
(benchmark/reference/ssm_window_shared_kv_decoder.py, loaded by path: it
imports nothing of the program), at the tiny ``shared_kv_test`` preset:
hidden 64, ``MWMWMWMFGXGX`` (3 x MW, M, F, 2 x GX), Mamba-1 of 128
channels over a state of 8 with a time-step rank of 4, 8 query heads of 8
on 4 K/V heads paired differentially, a window (and ring) of 16 positions.
Every tolerance carries its reason.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu import models
from distributed_llm_tpu.config import (MODEL_PRESETS, TierConfig,
                                        tiny_batched_cluster)
from distributed_llm_tpu.engine import paged_kv
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.models import shared_kv_hybrid as skv
from distributed_llm_tpu.models import transformer
from test_latent_moe import _while_depth

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The keys of the tiny preset, as the reference reads them.
TINY = {
    "hidden_size": 64, "num_hidden_layers": 12,
    "layer_pattern": "MWMWMWMFGXGX", "vocab_size": 512,
    "intermediate_size": 96, "num_attention_heads": 8,
    "num_key_value_heads": 4, "sliding_window": 16, "mamba_expand": 2,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 4,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "layer_norm_eps": 1e-5, "torch_dtype": "float32",
}
SEED = 3
TABLE = jnp.arange(1, 9, dtype=jnp.int32)
# 100 ids: 72 of prompt (chunks of 16: four whole and 8 + 8 of padding;
# the first four self-only) and 28 decode steps: the 16-position rings
# wrap six times, the state crosses every chunk edge.
TOKENS = np.random.default_rng(0).integers(0, 500, 100).astype(np.int32)
N_PROMPT = 72


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "shared_kv_reference", os.path.join(
            ROOT, "benchmark", "reference",
            "ssm_window_shared_kv_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(dtype="float32", **kw):
    return dataclasses.replace(MODEL_PRESETS["shared_kv_test"], dtype=dtype,
                               **kw)


def _params(cfg, seed=SEED):
    return jax.jit(lambda s: models.init_params(cfg, s))(jnp.int32(seed))


def _pool(cfg, slots=2):
    return paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=16, max_slots=slots, max_seq_len=128))


_CHUNKS = {}


def _chunk_fn(cfg):
    if cfg not in _CHUNKS:
        _CHUNKS[cfg] = jax.jit(
            lambda params, pool, piece, start, total, table:
            paged_kv.chunk_prefill_paged(cfg, params, piece, start, total,
                                         pool, table, 128))
    return _CHUNKS[cfg]


def _prefill(cfg, params, pool, tok, table=TABLE, chunk=16, pad=0):
    """``tok`` through the chunk program, the last chunk right-padded
    with ``pad``; returns (the last valid position's hidden, pool)."""
    for start in range(0, len(tok), chunk):
        piece = np.full((1, chunk), pad, np.int32)
        k = min(chunk, len(tok) - start)
        piece[0, :k] = tok[start:start + k]
        hidden, pool = _chunk_fn(cfg)(
            params, pool, jnp.asarray(piece), jnp.array([start]),
            jnp.array([len(tok)]), table)
    return hidden[0, k - 1], pool


def _serve(cfg, params, tok=TOKENS, n_prompt=N_PROMPT):
    """Chunked prefill of ``tok[:n_prompt]`` then teacher-forced decode of
    the rest, the sequence in batch slot 1 beside an idle slot 0; logits
    at positions n_prompt-1 ..."""
    last, pool = _prefill(cfg, params, _pool(cfg), tok[:n_prompt])
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


@pytest.fixture(scope="module")
def want(ref):
    return _reference(ref)


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    return _serve(cfg, _params(cfg))


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# (1) chunks then decode against the full forward ----------------------------

def test_float32_chunks_then_decode_match_the_reference(served, want):
    # Same numbers, another order of summation (a ring and a cache, the
    # merged form of the tick's attention): logits of size 75 agree to
    # 1e-4 absolute (1.1e-5 seen, 2e-7 of the norm); a ring slot or a
    # state carried wrongly reads 1e-2 of the norm and more.
    np.testing.assert_allclose(served, want, atol=1e-4, rtol=0)
    assert _rel(served, want) < 2e-6


def test_bfloat16_chunks_then_decode_stay_within_its_rounding(ref):
    cfg = _cfg("bfloat16")
    got = _serve(cfg, _params(cfg))
    want = _reference(ref, dict(TINY, torch_dtype="bfloat16"))
    # bfloat16 keeps 8 bits: every rounding is 2^-9 = 0.2% of its value;
    # through 12 layers of two sublayers the logits stay within 3% in
    # norm (0.7% seen).  A float32 answer would read 1e-7 here, a wrong
    # block several tens of percent.
    assert 1e-5 < _rel(got, want) < 3e-2


# (2) controls that must FAIL -------------------------------------------------

def _without(params, kind_key, change):
    """``params`` with ``change`` applied to leaf ``kind_key`` of every
    stacked layer that has it."""
    return {**params, "segments": [
        [{**lp, kind_key: change(lp[kind_key])} if kind_key in lp else lp
         for lp in seg] for seg in params["segments"]]}


@pytest.mark.parametrize("control", [
    "lambda zeroed", "D dropped from the memory", "a bias dropped",
    "window 15"])
def test_a_wrong_block_fails_the_float32_tolerance(control, want,
                                                   monkeypatch):
    cfg = _cfg()
    params = _params(cfg)
    if control == "lambda zeroed":
        # The second softmax of every pair never subtracted: lam = 0.
        real_c = skv.diff_combine
        monkeypatch.setattr(
            skv, "diff_combine", lambda cfg, lp, o, layer, dtype: real_c(
                cfg, lp, o.at[..., 1::2, :].set(0.0), layer, dtype))
    elif control == "D dropped from the memory":
        real = skv._mamba

        def no_d(cfg, lp, h_in, pool, li, ctx):
            out, new_pool, _ = real(cfg, lp, h_in, pool, li, ctx)
            bare = real(cfg, {**lp, "d": jnp.zeros_like(lp["d"])}, h_in,
                        pool, li, ctx)[2]
            return out, new_pool, bare
        monkeypatch.setattr(skv, "_mamba", no_d)
    elif control == "a bias dropped":
        params = _without(params, "b_o", jnp.zeros_like)
    else:
        cfg = _cfg(attn_window=15)
    _CHUNKS.pop(cfg, None)
    got = _serve(cfg, params)
    _CHUNKS.pop(cfg, None)
    # Each is a different function of the same weights: 1e-4 of the
    # logits' norm at the least (the stated path reads 2e-7), far over
    # the float32 tolerance of test (1).
    assert _rel(got, want) > 1e-4
    assert np.abs(got - want).max() > 1e-2


# (3) the one-step update is the chunk scan, position by position ------------

def test_one_step_update_equals_the_chunk_scan_position_by_position():
    cfg = _cfg()
    lp = skv.init_layer(cfg, jax.random.PRNGKey(SEED), "M")
    rng = np.random.default_rng(1)
    s_c, n_valid = 16, 11
    a = jnp.asarray(rng.normal(size=(s_c, cfg.ssm_inner)), jnp.float32)
    state0 = jnp.asarray(rng.normal(size=(cfg.ssm_state, cfg.ssm_inner)),
                         jnp.float32)
    tail0 = jnp.asarray(rng.normal(size=(cfg.ssm_conv - 1, cfg.ssm_inner)),
                        jnp.float32)
    m, state, tail = skv.ssm_scan(cfg, lp, a, state0, tail0,
                                  jnp.int32(n_valid))
    s, t, ms = state0[None], tail0[None], []
    for i in range(n_valid):
        mi, s, t = skv.ssm_step(cfg, lp, a[i][None], s, t,
                                jnp.array([True]))
        ms.append(mi[0])
    # One recurrence in one order, float32: 1e-6 of states of size 1 (the
    # two differ in how XLA fuses a step).  Positions past n_valid are
    # padding: not compared.
    np.testing.assert_allclose(np.asarray(m[:n_valid]), np.stack(ms),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s[0]),
                               atol=1e-5, rtol=0)
    # The tail is copied, never computed: the last 3 valid rows, exactly.
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(t[0]))
    np.testing.assert_array_equal(np.asarray(tail),
                                  np.asarray(a[n_valid - 3:n_valid]))
    # A row that is not valid keeps state and tail bit for bit.
    _, s2, t2 = skv.ssm_step(cfg, lp, a[0][None], s, t, jnp.array([False]))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(t))


def test_what_the_chunk_scans_kernel_serves_and_in_which_form():
    from distributed_llm_tpu.ops import ssm_chunk_scan
    assert ssm_chunk_scan.serves(24, 8, 256)
    assert not ssm_chunk_scan.serves(24, 8, 192)      # whole lane-widths
    assert not ssm_chunk_scan.serves(4096, 16, 5120)  # the chunk in VMEM
    assert ssm_chunk_scan.serves(256, 16, 5120)
    # Whole tiles of 8 positions and of 8 states (the operands' view).
    assert not ssm_chunk_scan.serves(20, 8, 256)
    assert not ssm_chunk_scan.serves(24, 12, 256)
    # Without the spreads of B and C a chunk may be longer than the 384
    # positions of the form before: 24.6 KB a position of 14 MB
    # (tests/test_tpu_compile.py compiles the longest for a v5e).
    assert ssm_chunk_scan.serves(512, 16, 5120)
    assert ssm_chunk_scan.serves(576, 16, 5120)
    assert not ssm_chunk_scan.serves(584, 16, 5120)
    # B and C whole in SMEM: 128 B a position of 256 KB.
    assert ssm_chunk_scan.serves(2048, 16, 128)
    assert not ssm_chunk_scan.serves(2056, 16, 128)
    # The form is the shape's alone: the widest of 8, 4, 2, 1 lane-widths
    # that divides the channels.
    assert [ssm_chunk_scan.lane_widths(c) for c in
            (128, 256, 384, 512, 768, 1024, 2048, 5120)
            ] == [1, 2, 1, 4, 2, 8, 8, 8]


@pytest.mark.parametrize("t,n,c,form", [
    (16, 8, 128, 1),       # one lane-width
    (24, 8, 256, 2),       # the tiny presets' widths
    (16, 8, 384, 1),       # three grid steps of one lane-width
    (16, 16, 512, 4),
    (16, 16, 1024, 8),     # the widest form: whole [8, 128] registers
    (24, 16, 2048, 8),     # and two grid steps of it, as 5120 has five
])
def test_the_chunk_scans_kernel_equals_the_unrolled_recurrence(t, n, c,
                                                               form):
    from distributed_llm_tpu.ops import ssm_chunk_scan
    assert ssm_chunk_scan.serves(t, n, c)
    assert ssm_chunk_scan.lane_widths(c) == form
    print(f"ssm_chunk_scan [{t}, {n}, {c}]: {form} lane-width(s) a grid "
          f"step, {c // (128 * form)} step(s)")
    rng = np.random.default_rng(2)
    valid = t - 5                     # padding starts INSIDE a tile of 8
    dt = jnp.asarray(np.abs(rng.normal(size=(t, c))) * 0.1, jnp.float32)
    dt = dt.at[valid:].set(0.0)
    u = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
    a = -jnp.asarray(1 + np.abs(rng.normal(size=(n, c))), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    for first in (jnp.zeros_like(s0), s0):       # a fresh row starts at 0
        y, s = ssm_chunk_scan.ssm_chunk_scan(dt, u, b, cm, a, first)
        y_x, s_x = skv.scan_unrolled(dt, u, b, cm, a, first)
        # One recurrence in one order, float32, two compilers and two
        # orders of a position's 16 products: 1e-5 of outputs of size 3
        # (2e-6 seen).
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_x),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_x),
                                   atol=1e-5, rtol=0)
    # Padding (dt = 0) neither decays nor feeds the state: the recurrence
    # over the valid positions alone, and the kernel with a whole tile of
    # padding more, bit for bit.
    _, s_valid = skv.scan_unrolled(dt[:valid], u[:valid], b[:valid],
                                   cm[:valid], a, s0)

    def pad(x):
        return jnp.concatenate([x, jnp.zeros((8,) + x.shape[1:])])
    y_pad, s_pad = ssm_chunk_scan.ssm_chunk_scan(
        pad(dt), pad(u), pad(b), pad(cm), a, s0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_valid),
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(s_pad), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(y_pad[:t]), np.asarray(y))
    # Two chained calls are the one call cut in two: bit for bit.
    y1, s1 = ssm_chunk_scan.ssm_chunk_scan(dt[:8], u[:8], b[:8], cm[:8],
                                           a, s0)
    y2, s2 = ssm_chunk_scan.ssm_chunk_scan(dt[8:], u[8:], b[8:], cm[8:],
                                           a, s1)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate([y1, y2])),
                                  np.asarray(y))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))


def test_a_program_that_calls_the_chunk_scan_13_times_traces_it_once(
        monkeypatch):
    # The kernel's body is 8 x state updates written out in Python: traced
    # a call, 13 calls a chunk program and a program a window rung, it was
    # 5 s a program of every engine's set-up (PR 47).  A shape no other
    # test of this process uses, so the count is this test's own.
    from distributed_llm_tpu.ops import ssm_chunk_scan
    traced = []
    kernel = ssm_chunk_scan._kernel
    monkeypatch.setattr(ssm_chunk_scan, "_kernel",
                        lambda *refs: traced.append(1) or kernel(*refs))
    t, n, c = 8, 8, 640
    dt, u = jnp.full((t, c), 0.1), jnp.ones((t, c))
    b = cm = jnp.ones((t, n))
    a, s0 = -jnp.ones((n, c)), jnp.zeros((n, c))

    @jax.jit
    def layers(state):
        for _ in range(13):
            _, state = ssm_chunk_scan.ssm_chunk_scan(dt, u, b, cm, a, state)
        return state
    layers(s0)
    jax.jit(lambda s: layers(s) * 2)(s0)          # and a second program
    assert len(traced) == 1


def test_a_state_rounded_to_bfloat16_at_rest_fails_that_tolerance():
    # The control for the state's precision is held HERE, on the state
    # itself: at this preset's weights the state adds a thousandth of the
    # memory, so its rounding moves the logits by less than float32's own
    # noise (2e-7 either way) and test (2) cannot see it.
    cfg = _cfg()
    lp = skv.init_layer(cfg, jax.random.PRNGKey(SEED), "M")
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(32, cfg.ssm_inner)), jnp.float32)
    tail0 = jnp.zeros((cfg.ssm_conv - 1, cfg.ssm_inner), jnp.float32)
    state0 = jnp.asarray(rng.normal(size=(cfg.ssm_state, cfg.ssm_inner)),
                         jnp.float32)
    whole = skv.ssm_scan(cfg, lp, a, state0, tail0, jnp.int32(32))

    def halves(rest):
        m1, s, t = skv.ssm_scan(cfg, lp, a[:16], state0, tail0,
                                jnp.int32(16))
        m2, s, t = skv.ssm_scan(cfg, lp, a[16:], rest(s), t, jnp.int32(16))
        return jnp.concatenate([m1, m2]), s
    m, s = halves(lambda s: s)
    # Two chunks are the one scan cut in two: bit for bit.
    np.testing.assert_array_equal(np.asarray(m), np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(whole[1]))
    m, s = halves(lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    # bfloat16 at rest: 2^-9 of states of size 1 is 2e-3, two hundred
    # times the 1e-5 of the test above.
    assert np.abs(np.asarray(s) - np.asarray(whole[1])).max() > 1e-3
    assert np.abs(np.asarray(m) - np.asarray(whole[0])).max() > 1e-4


# (4) padding, idle slots, a used row ----------------------------------------

def test_padding_and_an_idle_slot_leave_a_rows_memory_bit_identical():
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:40]
    _, a = _prefill(cfg, params, _pool(cfg), tok, pad=0)
    _, b = _prefill(cfg, params, _pool(cfg), tok, pad=77)
    # What the 8 padded positions of the last chunk hold never reaches
    # the state (their time step is 0), the tail (it stops at the last
    # valid row) or the rings (their writes are dropped).  Bit for bit.
    for key in ("s", "t", "rk", "rv", "owner"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))
    assert np.asarray(a["owner"]).tolist() == [1, 0]
    assert float(jnp.abs(a["rk"][:, 0]).sum()) > 0

    other = jnp.arange(9, 17, dtype=jnp.int32)
    pool = paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=16, max_slots=2, max_seq_len=128, pool_blocks=16))
    _, pool = _prefill(cfg, params, pool, tok)
    _, pool = _prefill(cfg, params, pool, tok[:20][::-1].copy(), table=other)
    assert np.asarray(pool["owner"]).tolist() == [1, 9]
    tables = jnp.stack([jnp.zeros(8, jnp.int32), other])
    after = pool
    for p in range(20, 24):
        _, after = paged_kv.decode_step_paged(
            cfg, params, jnp.asarray([5, 6]), jnp.array([p, p]), after,
            tables)
    # The first sequence's table is all trash in these steps: its rows
    # are idle, the second's move.
    for key in ("s", "t", "rk", "rv"):
        np.testing.assert_array_equal(np.asarray(after[key][:, 0]),
                                      np.asarray(pool[key][:, 0]))
        assert not np.array_equal(np.asarray(after[key][:, 1]),
                                  np.asarray(pool[key][:, 1]))


def test_a_sequence_admitted_into_a_used_row_starts_from_nothing():
    cfg = _cfg()
    params = _params(cfg)
    first, second = TOKENS[:40], TOKENS[40:64]
    want, clean = _prefill(cfg, params, _pool(cfg, 1), second)
    _, used = _prefill(cfg, params, _pool(cfg, 1), first)
    # The same blocks again: the chunk with start == 0 finds the row and
    # zeroes its state; the ring's stale rows are of positions "below 0"
    # to the new sequence and masked until it overwrites them.
    got, used = _prefill(cfg, params, used, second)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for key in ("s", "t"):
        np.testing.assert_array_equal(np.asarray(used[key]),
                                      np.asarray(clean[key]))


# (5) the self-only chunk -----------------------------------------------------

def test_self_only_chunks_leave_pool_and_last_logits_bit_identical(
        monkeypatch):
    cfg = _cfg()
    params = _params(cfg)
    tok = TOKENS[:N_PROMPT]
    last, pool = _prefill(cfg, params, _pool(cfg), tok)
    real = skv.chunk_ctx

    def every_chunk_full(*a):
        ctx, pool = real(*a)
        return {**ctx, "last": jnp.bool_(True)}, pool
    monkeypatch.setattr(skv, "chunk_ctx", every_chunk_full)
    _CHUNKS.pop(cfg)
    deep_last, deep = _prefill(cfg, params, _pool(cfg), tok)
    _CHUNKS.pop(cfg)
    # Every layer after F reads of other positions only F's K/V and
    # writes nothing: the same arrays, the same last hidden state.
    np.testing.assert_array_equal(np.asarray(last), np.asarray(deep_last))
    for key in pool:
        np.testing.assert_array_equal(np.asarray(pool[key]),
                                      np.asarray(deep[key]))
    # And a chunk that is not the last returns zeros, which no one reads.
    monkeypatch.undo()
    hidden, _ = _chunk_fn(cfg)(
        params, _pool(cfg), jnp.asarray(tok[None, :16]), jnp.array([0]),
        jnp.array([N_PROMPT]), TABLE)
    assert float(jnp.abs(hidden).max()) == 0.0


# (6) what the pool holds -----------------------------------------------------

def test_pool_holds_one_kv_layer_rings_and_rows_and_x_has_no_kv_weights():
    cfg = _cfg()
    pool = _pool(cfg, slots=3)
    assert pool["k"].shape == pool["v"].shape == (1, 25, 16, 32)
    assert pool["rk"].shape == pool["rv"].shape == (3, 3, 16, 32)
    assert pool["s"].shape == (4, 3, 8, 128) and \
        pool["s"].dtype == jnp.float32
    assert pool["t"].shape == (4, 3, 3, 128)
    # A window layer's memory a slot is the window, whatever the span.
    wide = paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=16, max_slots=3, max_seq_len=4096))
    assert wide["rk"].shape == pool["rk"].shape
    params = _params(cfg)
    assert cfg.layer_segments == (("MW", 3), ("M", 1), ("F", 1), ("GX", 2))
    gx = params["segments"][3]
    assert set(gx[0]) == {"ln1_w", "ln1_b", "ln2_w", "ln2_b", "w1", "w2",
                          "w_g1", "w_g2"}
    assert "w_qkv" not in gx[1] and gx[1]["wq"].shape == (2, 64, 64)
    assert params["segments"][2][0]["w_qkv"].shape == (1, 64, 128)
    assert "head" not in params                         # tied


def test_memory_unit_reads_the_same_tokens_memory_in_tick_and_chunk(
        monkeypatch):
    cfg = _cfg()
    params = _params(cfg)
    made, read = {}, []
    real_g, real_m = skv._gated_memory, skv._mamba

    def spy_m(cfg, lp, h_in, pool, li, ctx):
        out = real_m(cfg, lp, h_in, pool, li, ctx)
        jax.debug.callback(
            lambda li, m: made.__setitem__(int(li), np.asarray(m)),
            li, out[2])
        return out

    def spy_g(lp, h_in, mem):
        jax.debug.callback(lambda m: read.append(np.asarray(m)), mem)
        return real_g(lp, h_in, mem)
    monkeypatch.setattr(skv, "_mamba", spy_m)
    monkeypatch.setattr(skv, "_gated_memory", spy_g)
    hidden, pool = paged_kv.chunk_prefill_paged(
        cfg, params, jnp.asarray(TOKENS[None, :16]), jnp.array([0]),
        jnp.array([16]), _pool(cfg), TABLE, 128)
    jax.block_until_ready(hidden)
    # Both G layers read the scan output of the pattern's LAST M layer
    # (index 3 of 4), every position its own row: [1, 16, inner].
    assert sorted(made) == [0, 1, 2, 3] and len(read) == 2
    assert made[3].shape == (1, 16, 128)
    for mem in read:
        np.testing.assert_array_equal(mem, made[3])
    assert not np.array_equal(made[3], made[2])
    made.clear()
    read.clear()
    tables = jnp.stack([jnp.zeros(8, jnp.int32), TABLE])
    logits, _ = paged_kv.decode_step_paged(
        cfg, params, jnp.asarray([0, 7]), jnp.array([0, 16]), pool, tables)
    jax.block_until_ready(logits)
    assert len(read) == 2 and made[3].shape == (2, 1, 128)
    for mem in read:
        np.testing.assert_array_equal(mem, made[3])


# (7) the engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    MODEL_PRESETS["shared_kv_test_f32"] = _cfg(
        "float32", name="shared_kv_test_f32")
    tier = TierConfig(name="nano", model_preset="shared_kv_test_f32",
                      decode_batch=4, max_new_tokens=8, kv_block_size=16,
                      prefill_buckets=(16, 32, 64, 128),
                      prefill_chunk_tokens=16, decode_steps_per_tick=4,
                      enable_prefix_cache=False)
    eng = ContinuousBatchingEngine(tier, seed=SEED)
    yield eng
    eng.stop()
    del MODEL_PRESETS["shared_kv_test_f32"]


def test_tick_nests_two_whiles_and_the_chunk_program_one(engine,
                                                         monkeypatch):
    # The MODEL's loops.  On this CPU the chunk scan's kernel is
    # interpreted, its loop over the positions an HLO ``while`` of its
    # own; on the chip it is one custom call (the real programs, compiled
    # for a described v5e, are counted in tests/test_tpu_compile.py).
    from distributed_llm_tpu.ops import ssm_chunk_scan
    monkeypatch.setattr(ssm_chunk_scan, "serves", lambda *a: False)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tick = engine._decode_step().lower(
        engine.params, engine.pool, i32(4, 2), i32(4), i32(4),
        jax.ShapeDtypeStruct((4,), jnp.float32), key).compile()
    # Steps of a tick, repeats of a segment — and nothing inside a layer:
    # neither the ring, nor the one-step recurrence.
    assert _while_depth(tick.as_text()) == 2
    chunk = engine._chunk_prefill_fn(16, 128).lower(
        engine.params, engine.pool, i32(1, 16), i32(1), i32(1), i32(8),
        key, jax.ShapeDtypeStruct((), jnp.float32)).compile()
    # No loop over time, under the conditional around the layers after F
    # or outside it.
    text = chunk.as_text()
    assert _while_depth(text) == 1 and " conditional(" in text


def _greedy(ref, ids, n):
    weights = ref.init_weights(TINY, SEED)
    seq = np.zeros((1, len(ids) + n), np.int32)
    seq[0, :len(ids)] = ids
    out = []
    for p in range(len(ids), len(ids) + n):
        logits = ref.logits(TINY, weights, jnp.asarray(seq),
                            jnp.array([[p - 1]]))
        seq[0, p] = int(np.argmax(np.asarray(logits[0, 0])))
        out.append(int(seq[0, p]))
    return out


def test_engine_generates_the_references_greedy_tokens(engine, ref):
    long = ("a document of some length to read and think about, and then "
            "a question?")
    short = "briefly?"
    resets = engine.state_stats()["resets_total"]
    chunks = engine.prefill_stats()["chunks_total"]
    self_only = engine.prefill_stats()["chunks_self_only_total"]
    for prompt in (long, short):
        got = engine.generate(prompt, max_new_tokens=8)
        ids = engine.tokenizer.encode(prompt)
        if ids[0] != engine.tokenizer.bos_id:
            ids = [engine.tokenizer.bos_id] + list(ids)
        assert got.prompt_tokens == len(ids)
        # Float32 on both sides: the logits agree to 1e-5, so the argmax
        # does unless two logits tie that closely.
        want = _greedy(ref, ids, 8)
        cut = (want.index(engine.tokenizer.eos_id) + 1
               if engine.tokenizer.eos_id in want else 8)
        assert list(got.token_ids) == [
            t for t in want[:cut] if t != engine.tokenizer.eos_id]
    state = engine.state_stats()
    assert state["resets_total"] == resets + 2
    assert state["rows"] == 4 and state["rows_in_use"] == 0
    assert state["row_bytes"] == 4 * (8 * 128 * 4 + 3 * 128 * 4)
    assert (state["ring_layers"], state["ring_positions"]) == (3, 16)
    assert state["ring_bytes"] == 3 * 2 * 16 * 32 * 4
    # 73 ids are 5 chunks of 16, 4 of them self-only; 10 ids are one.
    pf = engine.prefill_stats()
    assert pf["chunks_total"] == chunks + 6
    assert pf["chunks_self_only_total"] == self_only + 4
    kv = engine.kv_stats()
    assert (kv["cached_layers"], kv["cache_readers"]) == (1, 3)
    from distributed_llm_tpu.obs import get_observability
    fam = get_observability().metrics.get(
        "dllm_prefill_self_only_chunks_total")
    assert fam.children()[("nano",)].value >= 4
    from distributed_llm_tpu.utils.telemetry import engine_stats
    stats = engine_stats(engine)
    assert stats["state"] == state and stats["kv"]["cache_readers"] == 3
    assert stats["decode_attention"] == "merged"
    assert stats["work"]["decode"]["hbm_bytes"] > 0


def test_chat_stream_serves_the_family_through_the_engine():
    from distributed_llm_tpu.obs import Observability
    from distributed_llm_tpu.serving.app import create_app
    from distributed_llm_tpu.serving.router import Router
    tiny = tiny_batched_cluster()
    cluster = dataclasses.replace(tiny, nano=dataclasses.replace(
        tiny.nano, model_preset="shared_kv_test", kv_block_size=16,
        prefill_buckets=(16, 32, 64, 128), prefill_chunk_tokens=16,
        enable_prefix_cache=False, max_new_tokens=16))
    router = Router(strategy="token", benchmark_mode=True, cluster=cluster,
                    config={"token_threshold": 1000000},
                    observability=Observability(slow_ms=None))
    try:
        client = create_app(router=router).test_client()
        resp = client.post("/chat/stream", json={
            "message": "a question of more than one chunk of sixteen ids",
            "strategy": "token", "session_id": "skv"})
        assert resp.status_code == 200 and '"done"' in resp.text
        assert '"device": "nano"' in resp.text
        stats = client.get("/stats").get_json()["tiers"]["nano"]
        assert stats["state"]["ring_layers"] == 3
        assert stats["kv"]["cached_layers"] == 1
        assert stats["prefill"]["chunks_self_only_total"] >= 2
    finally:
        router.drain()


@pytest.mark.parametrize("what,kw", [
    ("kv_quantize", dict(kv_quantize="int8")),
    ("draft_preset", dict(draft_preset="draft_test")),
    ("host_kv_bytes", dict(host_kv_bytes=1 << 20)),
    ("tensor-parallel", dict(tp=2)),
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("prefill_chunk_tokens=48", dict(prefill_chunk_tokens=48)),
    ("fits the window's ring of 16", dict(prefill_chunk_tokens=32)),
])
def test_unsupported_combinations_raise_by_name(what, kw):
    kw = {"enable_prefix_cache": False, "prefill_chunk_tokens": 16, **kw}
    tier = TierConfig(name="nano", model_preset="shared_kv_test",
                      decode_batch=2, kv_block_size=16,
                      prefill_buckets=(16, 32, 64, 128), **kw)
    mesh = None
    if "tp" in kw:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="shared-K/V hybrid family") as e:
        ContinuousBatchingEngine(tier, seed=0, mesh=mesh)
    assert what in str(e.value)


def test_the_block_programs_and_the_other_steps_refuse_the_pool_by_name():
    cfg = _cfg()
    with pytest.raises(ValueError, match="hybrid"):
        paged_kv.init_pool(cfg, paged_kv.PagedConfig(), "int8")
    pool = _pool(cfg)
    one = jnp.int32(1)
    for name, call in (
            ("copy_block", lambda: paged_kv.copy_block(pool, one, one)),
            ("gather_blocks",
             lambda: paged_kv.gather_blocks(pool, jnp.array([1])))):
        with pytest.raises(NotImplementedError, match=name):
            call()
    with pytest.raises(NotImplementedError, match="cold prefill"):
        models.serving_prefill(cfg, None, None, None)
    with pytest.raises(NotImplementedError, match="verify"):
        paged_kv.verify_step_paged(cfg, None, None, None, pool, None)
    with pytest.raises(ValueError, match="ONE F"):
        skv.check(dataclasses.replace(cfg, layer_pattern="MWMWMWMFGXGM"))
    with pytest.raises(ValueError, match="layer_pattern"):
        skv.check(dataclasses.replace(cfg, num_layers=9))


# (8) segments, int8 weights, the published init ------------------------------

@pytest.mark.parametrize("pattern,segments", [
    ("MWMWMWMWMWMWMWMWMFGXGXGXGXGXGXGX",
     (("MW", 8), ("M", 1), ("F", 1), ("GX", 7))),
    ("MEMEM*EMEMEM*E", (("MEMEM*E", 2),)),
    ("MEM*EMEM*E", (("MEM*E", 2),)),
    ("MEMEM*E" * 5 + "MEMEMEM*E" + "MEMEMEME",
     (("MEMEM*E", 5), ("ME", 3), ("M", 1), ("*", 1), ("EM", 4), ("E", 1))),
])
def test_layer_segments_are_the_patterns_maximal_periodic_runs(pattern,
                                                               segments):
    cfg = dataclasses.replace(MODEL_PRESETS["hybrid_test"],
                              layer_pattern=pattern,
                              num_layers=len(pattern))
    assert cfg.layer_segments == segments
    assert "".join(p * r for p, r in cfg.layer_segments) == pattern
    # What the older family scans is derived from the segments: the
    # period of a pattern of one segment, else the pattern itself.
    assert cfg.layer_period == (segments[0][0] if len(segments) == 1
                                else pattern)
    assert cfg.family == ("shared_kv" if "F" in pattern else "hybrid")


@pytest.mark.parametrize("preset,family,rows", [
    ("nano_test", "dense", False), ("moe_test", "dense", False),
    ("latent_test", "latent", False), ("hybrid_test", "hybrid", True),
    ("shared_kv_test", "shared_kv", True),
])
def test_one_name_a_family_and_rows_for_both_that_keep_them(preset, family,
                                                            rows):
    cfg = MODEL_PRESETS[preset]
    assert cfg.family == family and cfg.hybrid is rows
    assert (cfg.latent, cfg.shared_kv) == (family == "latent",
                                           family == "shared_kv")
    assert models.model_module(cfg).__name__.endswith(
        {"dense": "moe" if cfg.num_experts > 1 else "transformer",
         "latent": "latent_moe", "hybrid": "hybrid_ssm",
         "shared_kv": "shared_kv_hybrid"}[family])


def test_a_compiled_program_refuses_a_chunk_the_kernel_does_not_serve(
        monkeypatch):
    from distributed_llm_tpu.ops import pallas_attention
    cfg = _cfg()
    lp = jax.tree.map(lambda a: a[0], _params(cfg)["segments"][0][0])
    a = jnp.zeros((12, cfg.ssm_inner), jnp.float32)      # 12: not eights
    state = jnp.zeros((cfg.ssm_state, cfg.ssm_inner), jnp.float32)
    tail = jnp.zeros((cfg.ssm_conv - 1, cfg.ssm_inner), jnp.float32)
    # On the CPU the unrolled form stands in (the tests' yardstick) ...
    m, _, _ = skv.ssm_scan(cfg, lp, a, state, tail, jnp.int32(12))
    assert m.shape == (12, cfg.ssm_inner)
    # ... a compiled program has the kernel or nothing.
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    with pytest.raises(ValueError, match="ssm_chunk_scan does not serve"):
        skv.ssm_scan(cfg, lp, a, state, tail, jnp.int32(12))


def test_int8_weights_reach_the_familys_matrices():
    from distributed_llm_tpu.ops.quant import is_quantized, quantize_params
    cfg = _cfg()
    params = _params(cfg)
    q = jax.jit(quantize_params)(params)
    assert is_quantized(q["embed"])
    m, w = q["segments"][0]
    f, = q["segments"][2]
    g, x = q["segments"][3]
    assert all(is_quantized(m[k]) for k in ("w_in", "w_out", "w1", "w2"))
    assert all(is_quantized(lp[k]) for lp in (w, f)
               for k in ("w_qkv", "wo"))
    assert all(is_quantized(g[k]) for k in ("w_g1", "w_g2"))
    assert all(is_quantized(x[k]) for k in ("wq", "wo"))
    assert not any(is_quantized(m[k]) for k in ("conv_w", "a_log", "w_x",
                                                "w_dt", "ln1_b"))
    full = _serve(cfg, params, TOKENS[:60], n_prompt=40)
    low = _serve(cfg, q, TOKENS[:60], n_prompt=40)
    # int8 keeps 7 bits a weight: percent-level logits, never float32's
    # 1e-7 and never a wrong block's tens of percent.
    assert 1e-4 < _rel(low, full) < 0.1


def test_published_init_and_lambda_depth():
    cfg = _cfg()
    lp = skv.init_layer(cfg, jax.random.PRNGKey(0), "M")
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    # A = 1..state a channel, resting [state, inner]; D = 1.
    np.testing.assert_allclose(np.exp(np.asarray(lp["a_log"]))[:, 5],
                               np.arange(1, 9), rtol=1e-6)
    assert np.asarray(lp["d"]).tolist() == [1.0] * 128
    assert lp["a_log"].dtype == lp["dt_bias"].dtype == jnp.float32
    # Every bias is drawn, none is zero.
    at = skv.init_layer(cfg, jax.random.PRNGKey(0), "W")
    for lp_, keys in ((lp, ("ln1_b", "ln2_b", "conv_b")),
                      (at, ("b_qkv", "b_o", "ln1_b"))):
        for key in keys:
            assert float(jnp.abs(lp_[key]).min()) > 0
    assert at["lam"].shape == (4, 8) and at["sub_w"].shape == (16,)
    np.testing.assert_allclose(float(skv.lambda_init(0)), 0.2, rtol=1e-6)
    np.testing.assert_allclose(float(skv.lambda_init(17)),
                               0.8 - 0.6 * np.exp(-5.1), rtol=1e-6)


def test_roofline_counts_the_familys_matrices_rings_state_and_readers():
    from distributed_llm_tpu.utils import roofline
    cfg = _cfg("bfloat16")
    h, di, f = 64, 128, 96
    ssm = h * 2 * di + di * (4 + 16) + 4 * di + di * h
    attn = h * (64 + 2 * 32) + 64 * h
    body = (4 * ssm + 4 * attn + 2 * 2 * h * 64 + 2 * 2 * h * di
            + 12 * 3 * h * f)
    tables = (512 * 64 + (4 * 12 + 2) * 64) * 2
    assert roofline.weight_bytes(cfg) == body * 2 + tables
    assert roofline.weight_bytes(cfg, "int8") == body + tables
    assert roofline.active_matmul_params(cfg) == body + 512 * 64
    # K and V of the ONE cached layer, read by F and the two X layers.
    assert roofline.kv_bytes_per_pos(cfg) == 2 * 4 * 8 * 2
    assert roofline.kv_readers(cfg) == 3
    assert roofline.kv_readers(MODEL_PRESETS["hybrid_test"]) == 1
    assert roofline.state_row_bytes(cfg) == 4 * (128 * 8 * 4 + 3 * 128 * 2)
    assert roofline.ring_row_bytes(cfg) == 3 * 2 * 16 * 32 * 2
    a = roofline.decode_work(cfg, steps=2, ctx=64, batch=3)
    assert a["hbm_bytes"] == 2 * (
        roofline.weight_bytes(cfg)
        + 3 * 3 * roofline.kv_bytes_per_pos(cfg) * 64
        + 3 * 2 * roofline.state_row_bytes(cfg)
        + 3 * roofline.ring_row_bytes(cfg))
    assert paged_kv.pool_block_bytes(cfg, 16) == 1 * 4 * 16 * 8 * 2 * 2
