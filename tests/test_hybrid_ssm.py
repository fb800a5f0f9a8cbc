"""The state-space / attention / routed-expert hybrid family
(models/hybrid_ssm.py) against its plain float32 reference
(benchmark/reference/hybrid_ssm_moe_decoder.py, loaded by path: it
imports nothing of the program), at the tiny ``hybrid_test`` preset:
hidden 64, two periods of ``MEM*E``, 8 state-space heads of 16 in 2
groups over a state of 16, 4 query heads of 32 on 2 K/V heads, a router
over 8 outputs of which the first 4 are held, 3 a token, a shared expert.
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
from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
from distributed_llm_tpu.engine import paged_kv
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.models import hybrid_ssm, latent_moe, transformer
from distributed_llm_tpu.ops import grouped_product
from test_latent_moe import _while_depth

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The published keys of the tiny preset, as the reference reads them.
TINY = {
    "hidden_size": 64, "num_hidden_layers": 10,
    "hybrid_override_pattern": "MEM*EMEM*E", "vocab_size": 512,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4, "norm_eps": 1e-5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "n_routed_experts": 4, "router_outputs": 8, "first_routed_expert": 0,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "torch_dtype": "float32",
}
SEED = 3


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "hybrid_reference", os.path.join(
            ROOT, "benchmark", "reference", "hybrid_ssm_moe_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(dtype="float32", **kw):
    return dataclasses.replace(MODEL_PRESETS["hybrid_test"], dtype=dtype,
                               **kw)


def _params(cfg, seed=SEED):
    # The seed as an argument of the jitted maker, as the engine makes them.
    return jax.jit(lambda s: models.init_params(cfg, s))(jnp.int32(seed))


def _pool(cfg, slots=2):
    return paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=16, max_slots=slots, max_seq_len=128))


TABLE = jnp.arange(1, 9, dtype=jnp.int32)


def _prefill(cfg, params, pool, tok, table=TABLE, chunk=16, pad=0):
    """``tok`` through the chunk program, the last chunk right-padded
    with ``pad``; returns (the last valid position's hidden, pool)."""
    for start in range(0, len(tok), chunk):
        piece = np.full((1, chunk), pad, np.int32)
        k = min(chunk, len(tok) - start)
        piece[0, :k] = tok[start:start + k]
        hidden, pool = paged_kv.chunk_prefill_paged(
            cfg, params, jnp.asarray(piece), jnp.array([start]),
            jnp.array([len(tok)]), pool, table, 64)
    return hidden[0, k - 1], pool


def _serve(cfg, params, tok, n_prompt):
    """Chunked prefill of ``tok[:n_prompt]`` then teacher-forced decode of
    the rest, the sequence in batch slot 1 beside an idle slot 0; logits
    at positions n_prompt-1 ..."""
    last, pool = _prefill(cfg, params, _pool(cfg), tok[:n_prompt])
    out = [transformer.logits_from_hidden(params, last)]
    tables = jnp.stack([jnp.zeros(8, jnp.int32), TABLE])
    for p in range(n_prompt, len(tok)):
        logits, pool = paged_kv.decode_step_paged(
            cfg, params, jnp.asarray([0, tok[p]]), jnp.array([0, p]), pool,
            tables)
        out.append(logits[1])
    return np.stack([np.asarray(x, np.float32) for x in out])


# (1) -------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_prefill_then_decode_matches_reference(ref, dtype):
    cfg = _cfg(dtype)
    tok = np.random.default_rng(0).integers(0, 500, 60).astype(np.int32)
    # 40 ids: chunks of 16, 16 and 8 + 8 of padding; then 20 decode steps.
    got = _serve(cfg, _params(cfg), tok, n_prompt=40)
    model = dict(TINY, torch_dtype=dtype)
    want = np.asarray(ref.logits(
        model, ref.init_weights(model, SEED), jnp.asarray(tok[None]),
        jnp.arange(39, 60)[None]))[0]
    if dtype == "float32":
        # Same numbers, another order of summation (the recurrence in
        # matrix form, a cache, grouped experts): logits of size 0.6
        # agree to 1e-5 (2e-7 seen); a state carried wrongly reads 1e-2.
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    else:
        # bfloat16 keeps 8 bits: every rounding is 2^-9 = 0.2% of its
        # value; through 10 mixers the logits stay within 3% in norm
        # (0.6% seen).  A float32 answer would read 1e-7 here, a wrong
        # block several tens of percent.
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert 1e-5 < err < 3e-2


# (2) -------------------------------------------------------------------------

def test_matrix_form_over_a_chunk_equals_the_stepwise_recurrence():
    cfg = _cfg()
    lp = hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(SEED), "M")
    rng = np.random.default_rng(1)
    s_c, n_valid = 16, 11
    xbc = jnp.asarray(rng.normal(size=(s_c, cfg.ssm_conv_width)),
                      jnp.float32)
    dt = jnp.asarray(rng.normal(size=(s_c, cfg.ssm_heads)), jnp.float32)
    state0 = jnp.asarray(rng.normal(size=(cfg.ssm_heads, cfg.ssm_head_dim,
                                          cfg.ssm_state)), jnp.float32)
    tail0 = jnp.asarray(rng.normal(size=(cfg.ssm_conv - 1,
                                         cfg.ssm_conv_width)), jnp.float32)
    y, state, tail = hybrid_ssm.ssm_scan(cfg, lp, xbc, dt, state0, tail0,
                                         jnp.int32(n_valid))
    s, t, ys = state0[None], tail0[None], []
    for i in range(n_valid):
        yi, s, t = hybrid_ssm.ssm_step(cfg, lp, xbc[i][None], dt[i][None],
                                       s, t, jnp.array([True]))
        ys.append(yi[0])
    # One recurrence, summed in two orders, float32: states of size 1
    # agree to 1e-5.  Positions past n_valid are padding: not compared.
    np.testing.assert_allclose(np.asarray(y[:n_valid]), np.stack(ys),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s[0]),
                               atol=1e-5, rtol=0)
    # The tail is copied, never computed: the last 3 valid rows, exactly.
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(t[0]))
    np.testing.assert_array_equal(np.asarray(tail),
                                  np.asarray(xbc[n_valid - 3:n_valid]))


# (3) -------------------------------------------------------------------------

def test_padding_and_an_idle_slot_leave_a_rows_state_bit_identical():
    cfg = _cfg()
    params = _params(cfg)
    tok = np.random.default_rng(2).integers(0, 500, 40).astype(np.int32)
    _, a = _prefill(cfg, params, _pool(cfg), tok, pad=0)
    _, b = _prefill(cfg, params, _pool(cfg), tok, pad=77)
    # What the 8 padded positions of the last chunk hold never reaches
    # the state: their time step is 0, the tail stops at the last valid
    # row.  Bit for bit.
    for key in ("s", "t", "owner"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))
    assert np.asarray(a["owner"]).tolist() == [1, 0]
    assert float(jnp.abs(a["s"][:, 0]).sum()) > 0

    # A second sequence takes the free row; then decode steps in which
    # the first is idle (its table all trash) and the second live.
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
    for key in ("s", "t"):
        np.testing.assert_array_equal(np.asarray(after[key][:, 0]),
                                      np.asarray(pool[key][:, 0]))
        assert not np.array_equal(np.asarray(after[key][:, 1]),
                                  np.asarray(pool[key][:, 1]))


# (4) -------------------------------------------------------------------------

def test_a_sequence_admitted_into_a_used_row_starts_from_zero_state():
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(3)
    first = rng.integers(0, 500, 40).astype(np.int32)
    second = rng.integers(0, 500, 24).astype(np.int32)
    want, clean = _prefill(cfg, params, _pool(cfg, 1), second)
    _, used = _prefill(cfg, params, _pool(cfg, 1), first)
    # The same blocks again (the allocator hands a finished sequence's
    # blocks on): the chunk with start == 0 finds the row and zeroes it.
    got, used = _prefill(cfg, params, used, second)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for key in ("s", "t"):
        np.testing.assert_array_equal(np.asarray(used[key]),
                                      np.asarray(clean[key]))


# (5), (6) --------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_references_whole_layer(ref):
    whole = _cfg(experts_first=0, experts_count=8)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 64)),
                    jnp.float32)
    key = jax.random.PRNGKey(SEED)
    lp = hybrid_ssm.init_layer(whole, key, "E")
    parts, counts = [], []
    for first in (0, 4):
        cfg = _cfg(experts_first=first, experts_count=4)
        share = hybrid_ssm.init_layer(cfg, key, "E")
        # An expert's matrix is the same whichever share holds it.
        np.testing.assert_array_equal(
            np.asarray(share["we_up"]),
            np.asarray(lp["we_up"][first:first + 4]))
        # The sigmoid router carries nothing: a zero-wide state.
        out, n, _ = hybrid_ssm._experts(cfg, share, x[None], None, None,
                                        jnp.zeros((1, x.shape[0], 0)))
        parts.append(np.asarray(out[0]))
        counts.append(np.asarray(n[0] if n.ndim > 1 else n))
    shared = np.asarray(hybrid_ssm.shared_expert(lp, x))
    model = dict(TINY, n_routed_experts=8)
    # The program stores the experts' matrices zero-padded to whole
    # lane-widths (the test below); the reference takes them at the
    # published sizes.
    cut = dict(lp, we_up=lp["we_up"][:, :64, :32],
               we_down=lp["we_down"][:, :32, :64])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts_layer(model, cut, x))
        routed = np.asarray(ref.experts_layer(model, cut, x, shared=False))
    # Experts 0-3 and 4-7, the shared expert counted once: the whole
    # layer.  Float32 sums in another order: 1e-5 of outputs of size 1.
    np.testing.assert_allclose(parts[0] + parts[1] - shared, want,
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(want - routed, shared, atol=1e-5, rtol=0)
    # Every assignment is held by exactly one share: 12 tokens x 3.
    assert counts[0][:4].sum() + counts[1][:4].sum() == 36
    assert counts[0][4] == counts[1][:4].sum()
    assert counts[1][4] == counts[0][:4].sum()


# (hidden, width) as published -> as stored: whole lane-widths (128) of
# each and no further.  The tiny preset; nemotron-3-nano-30b-a3b (stored
# 2816 x 2048 until ISSUE 55); kimi-linear-48b-a3b and zaya1-8b, which
# rest as published under either rule.
STORED = {(64, 32): (128, 128), (2688, 1856): (2688, 1920),
          (2304, 1024): (2304, 1024), (2048, 2048): (2048, 2048)}


@pytest.mark.parametrize("published", list(STORED), ids=lambda p: "%dx%d" % p)
def test_experts_rest_zero_padded_to_whole_lane_widths(published):
    (h, f), (h_st, f_st) = published, STORED[published]
    cfg = _cfg(experts_first=0, experts_count=8, hidden_size=h,
               moe_ffn_size=f)
    assert hybrid_ssm.expert_dims_stored(cfg) == (h_st, f_st)

    def layer():
        return hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(SEED), "E")
    # Shapes only at the published sizes: no array of that size is made.
    lp = jax.eval_shape(layer)
    assert lp["we_up"].shape == (8, h_st, f_st)
    assert lp["we_down"].shape == (8, f_st, h_st)
    if (h, f) == (64, 32):
        lp = layer()
        assert float(jnp.abs(lp["we_up"][:, :h, :f]).min()) > 0.0
        assert float(jnp.abs(lp["we_up"][:, h:]).max()) == 0.0
        assert float(jnp.abs(lp["we_up"][:, :, f:]).max()) == 0.0
        assert float(jnp.abs(lp["we_down"][:, f:]).max()) == 0.0
        assert float(jnp.abs(lp["we_down"][:, :, h:]).max()) == 0.0


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_an_expert_layer_reads_the_same_from_a_tree_padded_further(
        dtype, monkeypatch):
    """The zero rows and columns beyond the published widths carry no
    number of the layer: the tree as stored (128 x 128) and the same tree
    zero-padded on to 256 x 256, what ``expert_dims_stored`` gave until
    ISSUE 55, give the same output bit for bit, through the one kernel
    call and through ``ragged_dot`` alike."""
    cfg = _cfg(dtype, experts_first=0, experts_count=8)
    lp = hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(SEED), "E")
    assert lp["we_up"].shape == (8, 128, 128)
    wider = dict(lp,
                 we_up=jnp.pad(lp["we_up"], ((0, 0), (0, 128), (0, 128))),
                 we_down=jnp.pad(lp["we_down"], ((0, 0), (0, 128), (0, 128))))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(12, 64)),
                    jnp.float32)
    carry = jnp.zeros((12, 0))

    def through(tree):
        # ``routed_experts`` pads its rows to the rule's hidden width.
        monkeypatch.setattr(hybrid_ssm, "expert_dims_stored",
                            lambda cfg: tree["we_up"].shape[1:])
        return hybrid_ssm.routed_experts(cfg, tree, x, None, None, carry)
    for form in ("pallas_ffn", "ragged_dot"):
        assert latent_moe.grouped_product_form(
            cfg, [lp["we_up"][None], lp["we_down"][None]], 12) == form
        (out, counts, _), (out_w, counts_w, _) = through(lp), through(wider)
        assert float(jnp.abs(out).max()) > 1e-3
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(out_w, np.float32))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(counts_w))
        monkeypatch.setattr(grouped_product, "serves", lambda *a: False)


def test_router_weights_are_normalised_over_all_choices_whoever_holds_them(
        ref):
    x = jnp.asarray(np.random.default_rng(5).normal(size=(9, 64)),
                    jnp.float32)
    key = jax.random.PRNGKey(SEED)
    for first in (0, 4):
        cfg = _cfg(experts_first=first, experts_count=4)
        lp = hybrid_ssm.init_layer(cfg, key, "E")
        choice, w = latent_moe.route(cfg, lp, x)
        assert choice.shape == (9, 3) and int(choice.max()) < 8
        # The three chosen weigh 2.5 together, held here or not.
        np.testing.assert_allclose(np.asarray(w.sum(1)), 2.5, rtol=1e-6)
        # The reference's gate of the held columns is those weights.
        model = dict(TINY, first_routed_expert=first)
        g = np.asarray(ref.gates(model, lp, x))
        every = np.zeros((9, 8), np.float32)
        np.put_along_axis(every, np.asarray(choice), np.asarray(w), axis=1)
        np.testing.assert_allclose(g, every[:, first:first + 4], rtol=1e-6)


# (7) -------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    MODEL_PRESETS["hybrid_test_f32"] = _cfg("float32",
                                            name="hybrid_test_f32")
    tier = TierConfig(name="nano", model_preset="hybrid_test_f32",
                      decode_batch=4, max_new_tokens=8, kv_block_size=16,
                      prefill_buckets=(16, 32, 64, 128),
                      prefill_chunk_tokens=16, decode_steps_per_tick=4,
                      enable_prefix_cache=False)
    eng = ContinuousBatchingEngine(tier, seed=SEED)
    yield eng
    eng.stop()
    del MODEL_PRESETS["hybrid_test_f32"]


def test_tick_nests_two_whiles_and_the_chunk_program_one(engine,
                                                         monkeypatch):
    # The MODEL's loops.  On this CPU the grouped product's kernel is
    # interpreted, its loop over the touched groups an HLO ``while`` of
    # its own; on the chip it is one custom call (the real ticks, compiled
    # for a described v5e, are counted in tests/test_tpu_compile.py).
    from distributed_llm_tpu.ops import grouped_product
    monkeypatch.setattr(grouped_product, "serves", lambda *a: False)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tick = engine._decode_step().lower(
        engine.params, engine.pool, i32(4, 2), i32(4), i32(4),
        jax.ShapeDtypeStruct((4,), jnp.float32), key).compile()
    assert engine._decode_step().__name__ == "decode_tick"
    # Steps of a tick, periods of a step — and nothing inside a period:
    # neither the seven kinds nor the one-step recurrence.
    assert _while_depth(tick.as_text()) == 2
    chunk = engine._chunk_prefill_fn(16, 128).lower(
        engine.params, engine.pool, i32(1, 16), i32(1), i32(1), i32(8),
        key, jax.ShapeDtypeStruct((), jnp.float32)).compile()
    # The chunk's recurrence is in matrix form: no loop over time.
    assert _while_depth(chunk.as_text()) == 1


# (9) -------------------------------------------------------------------------

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
    assert engine.pool["k"].shape[0] == 2      # the attention layers ONLY
    assert engine.pool["s"].shape[:2] == (4, 4) and \
        engine.pool["s"].dtype == jnp.float32
    before = engine.moe_stats()
    resets = engine.state_stats()["resets_total"]
    # The short prompt (under one chunk) after the long one, in a slot
    # and a row the long one used: through the chunk program, from zero.
    for prompt in (long, short):
        got = engine.generate(prompt, max_new_tokens=8)
        ids = engine.tokenizer.encode(prompt)
        if ids[0] != engine.tokenizer.bos_id:
            ids = [engine.tokenizer.bos_id] + list(ids)
        assert got.prompt_tokens == len(ids)
        # Float32 on both sides: the logits agree to 1e-6, so the argmax
        # does unless two logits tie that closely.
        want = _greedy(ref, ids, 8)
        cut = (want.index(engine.tokenizer.eos_id) + 1
               if engine.tokenizer.eos_id in want else 8)
        assert list(got.token_ids) == [
            t for t in want[:cut] if t != engine.tokenizer.eos_id]
    state = engine.state_stats()
    assert state["resets_total"] == resets + 2
    assert state["rows"] == 4 and state["rows_in_use"] == 0
    assert state["row_bytes"] == 4 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    after = engine.moe_stats()
    steps = after["steps"]["decode"] - before["steps"]["decode"]
    assert steps >= 8
    held = (np.asarray(after["expert_tokens"]["decode"]).sum()
            - np.asarray(before["expert_tokens"]["decode"]).sum())
    absent = (after["absent_assignments"]["decode"]
              - before["absent_assignments"]["decode"])
    # Every step of every tick: 4 slots x 3 choices x 4 expert layers,
    # held or absent.
    assert held + absent == steps * 4 * 3 * 4 and held > 0 and absent > 0
    assert np.asarray(after["expert_tokens"]["decode"]).shape == (4, 4)
    # GET /stats tiers.<tier> carries both, and the roofline work of the
    # ticks counts the state read and written.
    from distributed_llm_tpu.utils.telemetry import engine_stats
    stats = engine_stats(engine)
    assert stats["state"] == state
    assert stats["moe"]["absent_assignments"]["decode"] > 0
    assert stats["decode_attention"] in ("merged", "split")
    assert stats["work"]["decode"]["hbm_bytes"] > 0


# (8) -------------------------------------------------------------------------

@pytest.mark.parametrize("what,kw", [
    ("kv_quantize", dict(kv_quantize="int8")),
    ("draft_preset", dict(draft_preset="draft_test")),
    ("host_kv_bytes", dict(host_kv_bytes=1 << 20)),
    ("tensor-parallel", dict(tp=2)),
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("prefill_chunk_tokens", dict(prefill_chunk_tokens=0)),
    ("prefill_chunk_tokens=48", dict(prefill_chunk_tokens=48)),
])
def test_unsupported_combinations_raise_by_name(what, kw):
    kw = {"enable_prefix_cache": False, "prefill_chunk_tokens": 16, **kw}
    tier = TierConfig(name="nano", model_preset="hybrid_test",
                      decode_batch=2, kv_block_size=16,
                      prefill_buckets=(16, 32, 64, 128), **kw)
    mesh = None
    if "tp" in kw:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="state-space hybrid family") as e:
        ContinuousBatchingEngine(tier, seed=0, mesh=mesh)
    assert what in str(e.value)


def test_the_block_programs_and_the_other_steps_refuse_the_pool_by_name():
    cfg = _cfg()
    with pytest.raises(ValueError, match="state-space hybrid family"):
        paged_kv.init_pool(cfg, paged_kv.PagedConfig(), "int8")
    pool = _pool(cfg)
    one = jnp.int32(1)
    for name, call in (
            ("copy_block", lambda: paged_kv.copy_block(pool, one, one)),
            ("gather_blocks",
             lambda: paged_kv.gather_blocks(pool, jnp.array([1]))),
            ("scatter_blocks",
             lambda: paged_kv.scatter_blocks(pool, jnp.array([1]), {})),
            ("write_prefill_blocks",
             lambda: paged_kv.write_prefill_blocks(pool, jnp.array([1])))):
        with pytest.raises(NotImplementedError, match=name):
            call()
    with pytest.raises(NotImplementedError, match="cold prefill"):
        models.serving_prefill(cfg, None, None, None)
    with pytest.raises(NotImplementedError, match="verify"):
        paged_kv.verify_step_paged(cfg, None, None, None, pool, None)


# (10), (11) ------------------------------------------------------------------

def test_int8_weights_reach_the_familys_matrices():
    from distributed_llm_tpu.ops.quant import is_quantized, quantize_params
    cfg = _cfg()
    params = _params(cfg)
    q = jax.jit(quantize_params)(params)
    assert is_quantized(q["head"]) and is_quantized(q["embed"])
    m, e, _, a, _ = q["periods"]
    assert all(is_quantized(m[k]) for k in ("w_in", "w_out"))
    assert all(is_quantized(a[k]) for k in ("wq", "wk", "wv", "wo"))
    assert all(is_quantized(e[k]) for k in ("we_up", "we_down", "ws_up",
                                            "ws_down"))
    assert not any(is_quantized(m[k]) for k in ("conv_w", "a_log", "gn"))
    assert not is_quantized(e["router"])
    tok = np.random.default_rng(6).integers(0, 500, 44).astype(np.int32)
    full = _serve(cfg, params, tok, n_prompt=40)
    low = _serve(cfg, q, tok, n_prompt=40)
    err = np.linalg.norm(low - full) / np.linalg.norm(full)
    # int8 keeps 7 bits a weight: percent-level logits, never float32's
    # 1e-7 and never a wrong block's tens of percent.
    assert 1e-4 < err < 0.1


def test_roofline_counts_the_familys_matrices_state_and_attention_kv():
    from distributed_llm_tpu.utils import roofline
    cfg = _cfg("bfloat16")
    ssm = 64 * (128 + 192 + 8) + 128 * 64
    attn = 2 * 64 * 128 + 2 * 64 * 64
    fixed = 64 * 8 + 2 * 64 * 48
    expert = 2 * 64 * 32
    body = 4 * ssm + 2 * attn + 4 * (fixed + 4 * expert)
    # Every held expert, the two tables, the norms' gains (bf16).
    assert roofline.weight_bytes(cfg) == body * 2 + (
        2 * 512 * 64 + 11 * 64) * 2
    assert roofline.weight_bytes(cfg, "int8") == body + (
        2 * 512 * 64 + 11 * 64) * 2
    # A token's matmuls: 3 choices x the half of the router held here.
    assert roofline.active_matmul_params(cfg) == (
        4 * ssm + 2 * attn + 4 * fixed + int(4 * 1.5 * expert) + 512 * 64)
    # K and V of the 2 attention layers only; the rows beside them.
    assert roofline.kv_bytes_per_pos(cfg) == 2 * 2 * 2 * 32 * 2
    assert roofline.state_row_bytes(cfg) == 4 * (8 * 16 * 16 * 4
                                                 + 3 * 192 * 2)
    a = roofline.decode_work(cfg, steps=2, ctx=64, batch=3)
    assert a["hbm_bytes"] == 2 * (roofline.weight_bytes(cfg)
                                  + 3 * roofline.kv_bytes_per_pos(cfg) * 64
                                  + 3 * 2 * roofline.state_row_bytes(cfg))
    assert paged_kv.pool_block_bytes(cfg, 16) == 2 * 2 * 16 * 32 * 2 * 2


def test_the_layer_loop_scans_the_patterns_shortest_period():
    cfg = _cfg()
    assert cfg.layer_period == "MEM*E" and cfg.hybrid and not cfg.latent
    assert [cfg.layers_of(k) for k in "M*E"] == [4, 2, 4]
    assert hybrid_ssm.kind_index(cfg, "M") == ([0, 1, 1, 2, 2], 2)
    full = "MEMEM*E" * 5 + "MEMEMEM*E" + "MEMEMEME"
    big = dataclasses.replace(cfg, layer_pattern=full, num_layers=52)
    # The published 52 layers do not repeat: one period, scanned once.
    assert big.layer_period == full
    assert [big.layers_of(k) for k in "M*E"] == [23, 6, 23]
    cut = dataclasses.replace(cfg, layer_pattern=full[:14], num_layers=14)
    assert cut.layer_period == "MEMEM*E"
    with pytest.raises(ValueError, match="layer_pattern"):
        hybrid_ssm.check(dataclasses.replace(cfg, num_layers=9))
    with pytest.raises(ValueError, match="are not among the router's"):
        hybrid_ssm.check(dataclasses.replace(cfg, experts_first=6))
    # The published init: decays from a token to a thousand.
    lp = hybrid_ssm.init_layer(cfg, jax.random.PRNGKey(0), "M")
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    a = np.exp(np.asarray(lp["a_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    assert np.asarray(lp["d"]).tolist() == [1.0] * 8
