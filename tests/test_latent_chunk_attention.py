"""The latent chunk attention by blocks of the window (ISSUE 61,
``ops/latent_chunk_attention.py``), interpreted on the CPU at rehearsal
sizes: against the plain form of ``latent_moe._attend`` and a float64
attention, what ``serves`` admits of the three latent configurations'
warmed programs, and the price at start-up as counts.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS
from distributed_llm_tpu.models import latent_moe
from distributed_llm_tpu.ops import latent_chunk_attention as LCA

# Rehearsal widths: the latent numbers, a head's keys and its values a
# lane-width each (the least the kernel cuts by lane), 64 rotary numbers,
# a row resting 256 wide.
DC, DN, DR, DV, ROW = 128, 128, 64, 128, 256
SCALE = (DN + DR) ** -0.5


def _operands(b, s, w, n, seed=0, dtype=jnp.bfloat16, rotary=True):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_rope = jax.random.normal(keys[1], (b, s, n, DR), dtype)
    rows = jax.random.normal(keys[2], (b, w, ROW), dtype)
    if not rotary:
        # A pattern without rotary embedding: the shared numbers are read
        # as projected, whatever they are; here none.
        q_rope = jnp.zeros_like(q_rope)
    return (jax.random.normal(keys[0], (b, s, n, DN), dtype), q_rope, rows,
            (jax.random.normal(keys[3], (DC, n, DN + DV)) * DC ** -0.5
             ).astype(dtype))


def _plain(*operands):
    return LCA.plain(*operands, scale=SCALE)


def _float64(q_nope, q_rope, rows, w_kvb, q_pos):
    """The attention of the operands as given, nothing rounded."""
    q_nope, q_rope, rows, w_kvb = (np.asarray(x, np.float64)
                                   for x in (q_nope, q_rope, rows, w_kvb))
    kvb = np.einsum("bwc,cnd->bwnd", rows[..., :DC], w_kvb)
    scores = (np.einsum("bsnd,bwnd->bnsw", q_nope, kvb[..., :DN])
              + np.einsum("bsnr,bwr->bnsw", q_rope, rows[..., DC:DC + DR]))
    mask = (np.arange(rows.shape[1])[None, None, None, :]
            <= np.asarray(q_pos)[:, None, :, None])
    scores = np.where(mask, scores * SCALE, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bnsw,bwnd->bsnd", p, kvb[..., DN:])


# name: (B, S, W, N, the first query's position a sequence, rotary).
# ``blocking`` cuts a window of 4096 into 2 blocks of 2 pieces, one of
# 2048 into 1 block of 2 and one of 1280 into 1 block of 2 pieces of 640.
CASES = {
    "one-block": (1, 32, 256, 4, [224], True),
    "no-rotary-term": (1, 32, 512, 4, [480], False),
    # The chunk ends where its window does, a whole piece before it.
    "several-pieces": (1, 32, 2048, 2, [2016], True),
    "several-blocks": (1, 32, 4096, 2, [4064], True),
    # A rung wider than what is written: the second block is wholly the
    # chunk's future and is neither fetched nor multiplied.
    "a-block-wholly-masked": (1, 32, 4096, 2, [1000], True),
    # Each sequence its own end: one stops in the first block's first
    # piece, one in the second block.
    "two-sequences": (2, 16, 4096, 2, [700, 3000], True),
    "pieces-of-640": (1, 16, 1280, 4, [1100], True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_blocks_agree_with_the_plain_form_and_float64(case):
    b, s, w, n, starts, rotary = CASES[case]
    operands = _operands(b, s, w, n, rotary=rotary)
    q_pos = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(s)[None]
    got = LCA.latent_chunk_attention(*operands, q_pos, scale=SCALE)
    assert got.shape == (b, s, n, DV) and got.dtype == jnp.bfloat16
    got = np.asarray(got, np.float64)
    plain = np.asarray(_plain(*operands, q_pos), np.float64)
    exact = _float64(*operands, q_pos)
    # Both forms round the up-projection and the probabilities to
    # bfloat16: each lies as far from the exact attention as the other,
    # and they lie nearer each other than either to it.
    err_blocks, err_plain = (np.abs(x - exact).max() for x in (got, plain))
    assert err_blocks < 0.03, err_blocks
    assert err_blocks < 1.5 * err_plain + 1e-3, (err_blocks, err_plain)
    assert np.abs(got - plain).max() < 0.03


def test_a_chunk_slid_back_against_the_tables_end_and_a_capped_position():
    # ``chunk_prefill_paged`` caps a pad row's position at the prompt's
    # last token, and the lane slides the last chunk back so that it ends
    # with the table: positions repeat and do not end at W.
    b, s, w, n = 1, 32, 512, 4
    operands = _operands(b, s, w, n, seed=3)
    q_pos = jnp.minimum(w - s + jnp.arange(s), 500)[None].astype(jnp.int32)
    got = np.asarray(LCA.latent_chunk_attention(*operands, q_pos,
                                                scale=SCALE), np.float64)
    exact = _float64(*operands, q_pos)
    assert np.abs(got - exact).max() < 0.03
    assert np.abs(got - np.asarray(_plain(*operands, q_pos),
                                   np.float64)).max() < 0.03


def test_float32_rows_agree_to_float32():
    b, s, w, n = 1, 16, 1024, 2
    operands = _operands(b, s, w, n, seed=5, dtype=jnp.float32)
    q_pos = (900 + jnp.arange(s))[None].astype(jnp.int32)
    got = np.asarray(LCA.latent_chunk_attention(*operands, q_pos,
                                                scale=SCALE), np.float64)
    assert np.abs(got - _float64(*operands, q_pos)).max() < 2e-5


# -- which programs it takes -----------------------------------------------------

def _bench(config):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           config + ".json")) as f:
        return json.load(f)


# configuration: (heads, the lane's rungs, those of them that go by
# blocks, the prefix cache's reuse programs that do).  The rungs are
# ``_window_ladder``'s at the configuration's span; the widths the three
# share.
WARMED = {
    "xing4.0-29b-a4b": (32, (256, 1024, 2048, 4096, 8192),
                        (2048, 4096, 8192), {(64, 8192), (128, 8192)}),
    "kimi-linear-48b-a3b": (32, (256, 1024, 2048, 4096, 5120),
                            (2048, 4096, 5120), None),
    "sarvam-105b": (64, (256, 1024, 2048, 4096, 8192, 16384, 16640),
                    (1024, 2048, 4096, 8192, 16384, 16640), None),
}


@pytest.mark.parametrize("config", list(WARMED))
def test_serves_takes_the_programs_that_pay(config):
    """Of the chunk programs each latent configuration's engine warms,
    which go by blocks and which stay plain: ``serves``'s measured
    threshold on the plain form's temporaries (its docstring), never a
    model's name.  The lane's first rung stays plain everywhere (34 MB at
    64 heads: the plain form wins), its second at 32 heads (67 MB: a
    tie); every rung a long prompt spends its time at goes by blocks; a
    reuse suffix does only over the span's rung."""
    from distributed_llm_tpu.engine.batching import _window_ladder
    heads, rungs, blocks, reuse = WARMED[config]
    entry = _bench(config)
    tier = entry["tiers"]["nano"]["tier"]
    assert entry["num_attention_heads"] == heads
    span = max(tier["prefill_buckets"])
    assert tuple(_window_ladder(span, tier["kv_block_size"], True)) == rungs

    def form(s, w):
        return LCA.serves(s, w, heads, 128, 64, 128, 512, 640, jnp.bfloat16)
    assert tuple(w for w in rungs if form(256, w)) == blocks
    if reuse is not None:
        assert tier.get("enable_prefix_cache", True)
        warmed = {(sb, w) for sb in (64, 128)
                  for w in _window_ladder(span, tier["kv_block_size"], False)}
        assert {key for key in warmed if form(*key)} == reuse
    # No shape is admitted for fewer queries than a sublane tile holds, a
    # window that is not whole lane-widths, or an int8 pool.
    assert not form(8, 8192) and not form(256, 8192 + 64)
    assert not LCA.serves(256, 8192, heads, 128, 64, 128, 512, 640, jnp.int8)
    # What a step keeps fits the budget at every admitted shape.
    assert all(LCA.vmem_bytes(256, w, heads, 128, 64, 128, 512, 640, 2)
               <= LCA.VMEM_LIMIT // 2 for w in blocks)


def test_the_tiny_presets_stay_plain():
    # Every preset of config.py with a latent row: none holds whole
    # lane-widths of latent numbers, so the CPU suite's models run the
    # plain form, the kernel's reference.
    found = 0
    for name, cfg in MODEL_PRESETS.items():
        if not cfg.kv_lora_rank:
            continue
        found += 1
        assert latent_moe.chunk_attention_form(
            cfg, 16, 256, cfg.cache_row_rest_width, cfg.dtype) == "plain", name
    assert found >= 2


# -- the price at start-up, as counts --------------------------------------------

def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr its equations hold (a
    ``pallas_call``'s kernel, a loop's body)."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += _equations(inner)
    return total


def _abstract(b, s, w, n, dc=512, row=640):
    sds = jax.ShapeDtypeStruct
    return (sds((b, s, n, 128), jnp.bfloat16), sds((b, s, n, 64), jnp.bfloat16),
            sds((b, w, row), jnp.bfloat16),
            sds((dc, n, 256), jnp.bfloat16), sds((b, s), jnp.int32))


def test_the_kernels_jaxpr_does_not_grow_with_heads_window_or_chunk():
    def count(*shape):
        jaxpr = jax.make_jaxpr(
            lambda *a: LCA.latent_chunk_attention(*a, scale=SCALE))(
                *_abstract(*shape))
        return _equations(jaxpr.jaxpr)
    small, large = count(1, 256, 256, 4), count(1, 256, 16384, 64)
    assert small == large
    assert small < 200, small
    assert count(2, 64, 5120, 32) == small


def test_two_sites_of_a_program_trace_the_kernels_body_once(monkeypatch):
    # A chunk program holds the call at two sites of one shape (the inline
    # lead layer and the scan's body) and an engine warms a program a
    # window rung.  A shape no other test of this process uses, so the
    # count is this test's own.
    traced = []
    kernel = LCA._kernel
    monkeypatch.setattr(LCA, "_kernel", lambda *refs, **kw: (
        traced.append(1) or kernel(*refs, **kw)))
    b, s, w, n = 1, 48, 384, 2
    q_nope, q_rope, rows, w_kvb = _operands(b, s, w, n)
    q_pos = (300 + jnp.arange(s))[None].astype(jnp.int32)

    def site(q):
        return LCA.latent_chunk_attention(q, q_rope, rows, w_kvb, q_pos,
                                          scale=SCALE)

    @jax.jit
    def program(q):
        lead = site(q)                               # the inline layer

        def body(q, _):                              # the scan's body
            return site(q), None
        return jax.lax.scan(body, lead, None, length=3)[0]
    program(q_nope)
    jax.jit(lambda q: program(q) * 2)(q_nope)        # and a second program
    assert len(traced) == 1


# -- through ``_attend`` ------------------------------------------------------------

def test_attend_runs_the_kernel_where_serves_says_so(monkeypatch):
    """``latent_moe._attend`` at widths the kernel takes: the blocks form
    (``PAYS_FROM_BYTES`` lowered for the test's small shape) writes the
    same rows and attends as the plain form does, with rotary embedding
    and without."""
    cfg = dataclasses.replace(
        MODEL_PRESETS["latent_test"], num_heads=2, hidden_size=64,
        kv_lora_rank=DC, qk_nope_head_dim=DN, qk_rope_head_dim=DR,
        v_head_dim=DV, q_lora_rank=0, dtype="bfloat16")
    bs, nb, s, w = 16, 40, 32, 512
    keys = jax.random.split(jax.random.PRNGKey(7), 6)

    def normal(key, *shape):
        return (jax.random.normal(key, shape) * shape[0] ** -0.5
                ).astype(jnp.bfloat16)
    lp = {"wq": normal(keys[0], 64, 2 * (DN + DR)),
          "w_kva": normal(keys[1], 64, DC + DR),
          "kv_ln": jnp.ones((DC,), jnp.bfloat16),
          "w_kvb": normal(keys[2], DC, 2 * (DN + DV))}
    h_in = jax.random.normal(keys[3], (1, s, 64)).astype(jnp.bfloat16)
    pool_c = jax.random.normal(keys[4], (1, nb, bs, ROW)).astype(jnp.bfloat16)
    tables = (1 + jnp.arange(w // bs))[None]
    positions = 400 + jnp.arange(s)
    blk, off = tables[0][positions // bs][None], (positions % bs)[None]
    sin, cos = latent_moe.rope_sincos(cfg, positions[None])

    def attend(rope):
        return latent_moe._attend(cfg, lp, h_in, *(rope or (None, None)),
                                  positions[None], pool_c, 0, blk, off,
                                  tables, absorbed=False)
    assert latent_moe.chunk_attention_form(cfg, s, w, ROW,
                                           jnp.bfloat16) == "plain"
    plain = [attend(rope) for rope in ((sin, cos), None)]
    monkeypatch.setattr(LCA, "PAYS_FROM_BYTES", 0)
    assert latent_moe.chunk_attention_form(cfg, s, w, ROW,
                                           jnp.bfloat16) == "blocks"
    for (want, want_pool), rope in zip(plain, ((sin, cos), None)):
        got, got_pool = attend(rope)
        np.testing.assert_array_equal(np.asarray(got_pool, np.float32),
                                      np.asarray(want_pool, np.float32))
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32)).max() < 0.03


# -- the counter that says how often it engages -------------------------------------

@pytest.mark.parametrize("preset,form", [("latent_test", "plain"),
                                         ("nano_test", None)])
def test_the_engine_reports_each_chunk_programs_form(preset, form):
    """``prefill_stats()["attention_form"]`` names what every compiled
    chunk program attends its latent rows with, the tick's twin of
    ``tick_stats()["attention_form"]``; ``chunks_by_attention_form`` and
    ``dllm_prefill_chunks_by_attention_form_total`` count the lane's
    chunks by it; a family without a latent row reports neither."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    from distributed_llm_tpu.obs import get_observability
    tier = TierConfig(name="forms_" + preset, model_preset=preset,
                      decode_batch=2, kv_block_size=16,
                      prefill_buckets=(16, 32, 64, 128),
                      prefill_chunk_tokens=16, enable_prefix_cache=False,
                      max_new_tokens=2)
    engine = ContinuousBatchingEngine(tier)
    try:
        engine.generate("a prompt of more than one chunk of sixteen tokens",
                        max_new_tokens=2)
        stats = engine.prefill_stats()
        chunks = stats["chunks_total"]
        assert chunks >= 2
        programs = engine._compiled["chunk_prefill"]
        assert engine.chunk_attention_form(16, 256) == form
        counter = get_observability().m.prefill_chunks_by_form
        if form is None:
            assert stats["attention_form"] == {}
            assert stats["chunks_by_attention_form"] == {}
        else:
            assert stats["attention_form"] == {
                "%dx%d" % key: form for key in programs}
            assert stats["chunks_by_attention_form"] == {form: chunks}
            assert counter.labels(tier.name, form).value == chunks
        listed = engine.step_programs("chunk_prefill", ops=False)
        assert [e["attention_form"] for e in listed] == [form] * len(programs)
    finally:
        engine.stop()
