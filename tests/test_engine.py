"""Inference engine correctness on the CPU platform.

The key invariant (the one Ollama guaranteed for the reference and we must
guarantee ourselves): incremental decode with a KV cache produces the same
distribution as a full forward pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig, tiny_cluster
from distributed_llm_tpu.engine.inference import InferenceEngine
from distributed_llm_tpu.engine.tokenizer import ByteTokenizer
from distributed_llm_tpu.models import transformer


CFG = MODEL_PRESETS["nano_test"]


# -- tokenizer --------------------------------------------------------------

def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    text = "Hello, TPU! ünïcødé 你好"
    ids = tok.encode(text)
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == text


def test_tokenizer_history_format():
    tok = ByteTokenizer()
    hist = [{"role": "user", "content": "hi"},
            {"role": "assistant", "content": "hello"},
            {"role": "user", "content": "bye"}]
    assert tok.format_history(hist) == "user: hi\nassistant: hello\nuser: bye"
    assert tok.format_history("plain text") == "plain text"


# -- model ------------------------------------------------------------------

def test_param_shapes_and_count():
    params = transformer.init_params(CFG, seed=0)
    assert params["embed"].shape == (CFG.vocab_size, CFG.hidden_size)
    assert params["layers"]["wq"].shape == (
        CFG.num_layers, CFG.hidden_size, CFG.num_heads * CFG.head_dim)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == CFG.param_count()


def test_prefill_decode_equivalence():
    """Logits from incremental KV-cache decode must match full prefill."""
    params = transformer.init_params(CFG, seed=1)
    tokens = jnp.array([[257, 72, 101, 108, 108, 111, 33, 10]])  # BOS + bytes
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    hidden, _ = transformer.prefill(CFG, params, tokens, positions)
    full_logits = transformer.logits_from_hidden(params, hidden)  # [B,S,V]

    cache = transformer.init_kv_cache(CFG, b, 32)
    step_logits = []
    for i in range(s):
        logits, cache = transformer.decode_step(
            CFG, params, tokens[:, i], jnp.array([i]), cache)
        step_logits.append(logits)
    step_logits = jnp.stack(step_logits, axis=1)

    np.testing.assert_allclose(
        np.asarray(step_logits), np.asarray(full_logits), rtol=2e-2, atol=2e-2)


def test_prefill_is_causal():
    """Changing a later token must not affect earlier positions' logits."""
    params = transformer.init_params(CFG, seed=2)
    t1 = jnp.array([[257, 10, 20, 30, 40]])
    t2 = t1.at[0, 4].set(99)
    pos = jnp.arange(5)[None]
    h1, _ = transformer.prefill(CFG, params, t1, pos)
    h2, _ = transformer.prefill(CFG, params, t2, pos)
    np.testing.assert_allclose(np.asarray(h1[:, :4]), np.asarray(h2[:, :4]),
                               rtol=1e-5, atol=1e-5)


def test_padding_does_not_change_last_logits():
    """Right-padding a prompt up to a bucket must not change the logits at
    the last real position (what the engine samples from)."""
    params = transformer.init_params(CFG, seed=3)
    ids = [257, 72, 101, 108, 108]
    short = jnp.array([ids])
    padded = jnp.array([ids + [256] * 11])
    h_s, _ = transformer.prefill(
        CFG, params, short, jnp.arange(short.shape[1])[None])
    h_p, _ = transformer.prefill(
        CFG, params, padded, jnp.arange(padded.shape[1])[None])
    np.testing.assert_allclose(
        np.asarray(h_s[0, len(ids) - 1]), np.asarray(h_p[0, len(ids) - 1]),
        rtol=1e-5, atol=1e-5)


# -- engine -----------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(tiny_cluster().nano, seed=0)


def test_generate_returns_result(engine):
    r = engine.generate("user: say something")
    assert r.prompt_tokens > 0
    assert 0 <= r.gen_tokens <= engine.tier.max_new_tokens
    assert r.ttft_ms > 0 and r.total_ms >= r.ttft_ms
    assert isinstance(r.text, str)
    assert len(r.token_ids) == r.gen_tokens


def test_generate_deterministic_greedy(engine):
    a = engine.generate("user: hello there")
    b = engine.generate("user: hello there")
    assert a.token_ids == b.token_ids


def test_generate_from_history(engine):
    hist = [{"role": "user", "content": "hi"},
            {"role": "assistant", "content": "hello"},
            {"role": "user", "content": "what is 2+2?"}]
    r = engine.generate(hist)
    assert r.prompt_tokens > 10


def test_generate_respects_max_new_tokens(engine):
    r = engine.generate("user: count to one hundred", max_new_tokens=3)
    assert r.gen_tokens <= 3


def test_long_prompt_truncated_keeps_tail(engine):
    cap = engine.cfg.max_seq_len - engine.tier.max_new_tokens
    long_prompt = "x" * (cap * 3)
    r = engine.generate(long_prompt)
    assert r.prompt_tokens <= cap


def test_bucket_selection(engine):
    from distributed_llm_tpu.engine.inference import pick_bucket
    buckets, max_seq = engine.tier.prefill_buckets, engine.cfg.max_seq_len
    assert pick_bucket(buckets, 5, max_seq) == 16
    assert pick_bucket(buckets, 17, max_seq) == 32
    assert pick_bucket(buckets, 10_000, max_seq) == min(max(buckets), max_seq)


def test_prefill_jit_cached_per_bucket(engine):
    engine.generate("user: aaaa")
    engine.generate("user: " + "a" * 40)
    keyed = {k[0] for k in engine._prefill_fns if isinstance(k, tuple)
             and isinstance(k[0], int)}
    assert 16 in keyed and 32 in keyed
    # one decode program per cache length; both prompts share one length
    assert len(engine._decode_fns) == 1


def test_grow_fn_copies_prefix_and_zero_fills():
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.engine.inference import InferenceEngine
    from distributed_llm_tpu.models import transformer
    import jax.numpy as jnp
    import numpy as np

    tier = TierConfig(name="nano", model_preset="nano_test",
                      max_new_tokens=8, prefill_buckets=(16, 32, 64))
    eng = InferenceEngine(tier, seed=0)
    small = transformer.init_kv_cache(eng.cfg, 1, 32)
    small = {"k": small["k"].at[:, :, :5].set(1.0),
             "v": small["v"].at[:, :, :5].set(2.0)}
    big = eng._grow_fn(32, 64)(small)
    assert big["k"].shape[2] == 64
    np.testing.assert_array_equal(np.asarray(big["k"][:, :, :5]), 1.0)
    np.testing.assert_array_equal(np.asarray(big["v"][:, :, :5]), 2.0)
    np.testing.assert_array_equal(np.asarray(big["k"][:, :, 32:]), 0.0)


def test_long_prompt_chunked_prefill_matches_single_shot():
    """Prompts beyond the largest bucket prefill in chunks instead of
    being tail-truncated; output matches a single-shot engine whose
    bucket holds the whole prompt."""
    text = "user: " + " ".join(f"word{i}" for i in range(25))   # ~180 ids
    chunked = InferenceEngine(
        TierConfig(name="nano", model_preset="nano_test", max_new_tokens=8,
                   prefill_buckets=(16, 32, 64)), seed=40)
    single = InferenceEngine(
        TierConfig(name="nano", model_preset="nano_test", max_new_tokens=8,
                   prefill_buckets=(256,)), seed=40)
    r1 = chunked.generate(text)
    r2 = single.generate(text)
    assert r1.prompt_tokens == r2.prompt_tokens > 64   # nothing truncated
    assert r1.token_ids == r2.token_ids


def test_long_prompt_then_prefix_reuse():
    """A long chunked prompt parks its cache; the follow-up turn reuses it
    and only prefills the new turn."""
    eng = InferenceEngine(
        TierConfig(name="nano", model_preset="nano_test", max_new_tokens=8,
                   prefill_buckets=(16, 32, 64)), seed=41)
    text = "user: " + " ".join(f"item{i}" for i in range(22))
    r1 = eng.generate(text)
    assert r1.prompt_tokens > 64
    r2 = eng.generate(text + "\nassistant: " + (r1.text or "x")
                      + "\nuser: short follow up")
    assert eng.prefix_cache.stats()["hits"] == 1
    assert r2.prompt_tokens > r1.prompt_tokens


def test_long_suffix_reuse_chunks_from_matched_prefix():
    """A new turn LONGER than the largest bucket still reuses the parked
    prefix (chunk-strided from the matched position) and matches a cold
    engine token for token."""
    mk = lambda: TierConfig(name="nano", model_preset="nano_test",
                            max_new_tokens=8, prefill_buckets=(16, 32, 64))
    warm = InferenceEngine(mk(), seed=42)
    t1 = "user: " + " ".join(f"alpha{i}" for i in range(12))     # ~100 ids
    r1 = warm.generate(t1)
    follow = (t1 + "\nassistant: " + (r1.text or "x")
              + "\nuser: " + " ".join(f"beta{i}" for i in range(12)))
    r2 = warm.generate(follow)
    assert warm.prefix_cache.stats()["hits"] == 1
    import dataclasses
    cold = InferenceEngine(
        dataclasses.replace(mk(), enable_prefix_cache=False), seed=42)
    cold.generate(t1)                     # align rng consumption
    r2c = cold.generate(follow)
    assert r2.token_ids == r2c.token_ids
    assert r2.prompt_tokens == r2c.prompt_tokens > 64


def test_warmup_feeds_liveness_beats():
    """Every engine warmup fires its beat callback per compiled program
    (and EngineManager forwards it): on chip a full warmup is dozens of
    20-40 s compiles — silent, it would idle out a caller's wedge
    watchdog before serving starts."""
    from distributed_llm_tpu.config import tiny_cluster
    from distributed_llm_tpu.engine.manager import EngineManager

    beats = []
    mgr = EngineManager(tiny_cluster().nano, seed=0)
    mgr.start_server(beat=lambda: beats.append(1))
    try:
        # One beat per compiled program: at minimum the cold generate
        # plus each (bucket, rung) warm — the exact count tracks the
        # ladder, so pin only the floor.
        assert len(beats) >= 3, beats
    finally:
        mgr.stop_server()
