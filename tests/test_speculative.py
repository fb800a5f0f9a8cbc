"""Speculative decoding: chunked verify correctness and the exactness
guarantee (speculative output ≡ target-only greedy output)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import TierConfig
from distributed_llm_tpu.engine.inference import InferenceEngine
from distributed_llm_tpu.engine.speculative import (SpeculativeEngine,
                                                    decode_chunk)
from distributed_llm_tpu.models import transformer


def _tier(preset, **kw):
    defaults = dict(name="t", model_preset=preset, max_new_tokens=16,
                    prefill_buckets=(16, 32, 64))
    defaults.update(kw)
    return TierConfig(**defaults)


def test_decode_chunk_matches_sequential_steps():
    cfg = _tier("nano_test").model()
    params = transformer.init_params(cfg, seed=0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 255)
    start = jnp.asarray([3], jnp.int32)

    cache_a = transformer.init_kv_cache(cfg, 1, 32)
    logits_chunk, cache_a = decode_chunk(cfg, params, tokens, start, cache_a)

    cache_b = transformer.init_kv_cache(cfg, 1, 32)
    seq_logits = []
    for i in range(4):
        lg, cache_b = transformer.decode_step(
            cfg, params, tokens[:, i], start + i, cache_b)
        seq_logits.append(lg)
    seq_logits = jnp.stack(seq_logits, axis=1)          # [1, 4, V]

    np.testing.assert_allclose(np.asarray(logits_chunk),
                               np.asarray(seq_logits), atol=2e-2, rtol=2e-2)
    # Same greedy picks — the property the verifier relies on.
    np.testing.assert_array_equal(np.asarray(logits_chunk.argmax(-1)),
                                  np.asarray(seq_logits.argmax(-1)))
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(cache_a[key]),
                                   np.asarray(cache_b[key]), atol=1e-2)


def test_speculative_output_identical_to_target_greedy():
    """The exactness guarantee, with a draft model the target disagrees
    with constantly (independent random init)."""
    target = _tier("orin_test")
    engine_t = InferenceEngine(target, seed=7)
    ref = engine_t.generate("user: tell me about oceans",
                            max_new_tokens=12)

    spec = SpeculativeEngine(target, _tier("nano_test"), gamma=3, seed=7)
    got = spec.generate("user: tell me about oceans", max_new_tokens=12)
    assert got.token_ids == ref.token_ids
    assert got.text == ref.text


def test_speculative_accepts_everything_when_draft_is_target():
    target = _tier("nano_test")
    spec = SpeculativeEngine(target, target, gamma=4, seed=9,
                             draft_params=None)
    # Same preset and same seed salt would differ; force identical params.
    spec.params_d = spec.params_t
    ref = InferenceEngine(target, seed=9).generate("user: hi there",
                                                   max_new_tokens=12)
    got = spec.generate("user: hi there", max_new_tokens=12)
    assert got.token_ids == ref.token_ids
    assert spec.acceptance_rate == 1.0       # every draft token accepted


def test_speculative_respects_budget_and_reports_rate():
    spec = SpeculativeEngine(_tier("orin_test"), _tier("nano_test"),
                             gamma=2, seed=3)
    r = spec.generate("user: count", max_new_tokens=5)
    assert r.gen_tokens <= 5
    assert 0.0 <= spec.acceptance_rate <= 1.0


def test_speculative_rejects_temperature_and_vocab_mismatch():
    spec = SpeculativeEngine(_tier("orin_test"), _tier("nano_test"), seed=1)
    with pytest.raises(NotImplementedError):
        spec.generate("user: x", temperature=0.7)


def test_draft_cache_has_no_hole_after_full_accept():
    """With full acceptance the round advances γ+1 positions; the draft
    cache must have real K/V at every one of them (a zero hole at
    pos+γ would degrade all later drafting)."""
    target = _tier("nano_test")
    spec = SpeculativeEngine(target, target, gamma=3, seed=11)
    spec.params_d = spec.params_t            # guarantees full acceptance

    ids = spec.tokenizer.encode_history("user: abcd")
    n, bucket = len(ids), 16
    tokens = np.full((1, bucket), spec.tokenizer.pad_id, np.int32)
    tokens[0, :n] = ids
    first, cache_t, cache_d = spec._prefill_fn(bucket, spec._cache_lens[0])(
        spec.params_t, spec.params_d, jnp.asarray(tokens),
        jnp.asarray([n], np.int32))

    out, n_acc, cur, pos, cache_t, cache_d = spec._spec_step()(
        spec.params_t, spec.params_d, cache_t, cache_d,
        first.reshape(1), jnp.asarray([n], jnp.int32))
    assert int(n_acc[0]) == 3                # full accept
    for p in range(n, n + 4):                # pos .. pos+γ inclusive
        assert np.any(np.asarray(cache_d["k"])[:, 0, p] != 0), \
            f"draft cache hole at position {p}"


def test_manager_rejects_conflicting_speculative_config(caplog):
    import logging
    from distributed_llm_tpu.engine.manager import EngineManager
    from distributed_llm_tpu.engine.inference import InferenceEngine
    tier = _tier("nano_test", name="nano", draft_preset="nano_test",
                 temperature=0.7)
    mgr = EngineManager(tier, warmup_on_start=False)
    with caplog.at_level(logging.WARNING):
        engine = mgr.engine()
    assert isinstance(engine, InferenceEngine)   # fell back, loudly
    assert any("draft_preset" in r.message for r in caplog.records)
    mgr.stop_server()


def test_manager_builds_speculative_tier():
    from distributed_llm_tpu.engine.manager import EngineManager
    tier = _tier("orin_test", name="orin", draft_preset="nano_test",
                 speculative_gamma=3)
    mgr = EngineManager(tier, warmup_on_start=False)
    engine = mgr.engine()
    assert isinstance(engine, SpeculativeEngine)
    r = engine.generate("user: spec tier", max_new_tokens=4)
    assert isinstance(r.text, str)
    mgr.stop_server()


def test_speculative_stream_matches_generate():
    """generate() is built on generate_stream(); deltas concatenate to the
    result text and tokens match a fresh engine's generate()."""
    eng_a = SpeculativeEngine(_tier("orin_test"), _tier("nano_test"),
                              gamma=3, seed=41)
    eng_b = SpeculativeEngine(_tier("orin_test"), _tier("nano_test"),
                              gamma=3, seed=41)
    ref = eng_a.generate("user: stream the speculation", max_new_tokens=10)
    handle = eng_b.generate_stream("user: stream the speculation",
                                   max_new_tokens=10)
    text = "".join(handle)
    assert text == ref.text
    assert handle.result.token_ids == ref.token_ids


def test_fused_loop_matches_streaming_tokens():
    """generate() (one fused while_loop device call) and generate_stream()
    (one device call per round) must emit identical tokens — both are
    built on _round_body, and the fused emit/EOS/budget logic has to
    mirror the streaming host loop exactly."""
    import dataclasses

    from distributed_llm_tpu.config import default_checkpoint, tiny_cluster

    tgt = dataclasses.replace(tiny_cluster().orin, tp=1, temperature=0.0,
                              checkpoint_path=default_checkpoint("orin_test"))
    dr = dataclasses.replace(tiny_cluster().nano, name="draft",
                             temperature=0.0)
    se = SpeculativeEngine(tgt, dr, gamma=3, seed=2)
    for prompt, mx in [("user: what is the largest ocean?", 12),
                       ("user: hi", 4),
                       ("user: name a mountain and a river and explain "
                        "both in a sentence", 8)]:
        g = se.generate(prompt, max_new_tokens=mx)
        h = se.generate_stream(prompt, max_new_tokens=mx)
        for _ in h:
            pass
        assert g.token_ids == h.request.result.token_ids, prompt
        assert g.gen_tokens <= mx


def test_speculative_with_int8_weights_paths_agree():
    """Speculative serving under weight-only int8 (the bench_cluster
    default): the fused loop and the streaming path still emit identical
    tokens, and the exactness guarantee vs the plain int8 engine holds."""
    target = _tier("orin_test", quantize="int8", temperature=0.0)
    draft = _tier("nano_test", temperature=0.0)
    spec = SpeculativeEngine(target, draft, gamma=3, seed=5)
    ref = InferenceEngine(target, seed=5)
    prompt = "user: quantized speculation?"
    g = spec.generate(prompt, max_new_tokens=10)
    h = spec.generate_stream(prompt, max_new_tokens=10)
    for _ in h:
        pass
    assert g.token_ids == h.request.result.token_ids
    assert g.token_ids == ref.generate(prompt, max_new_tokens=10).token_ids
