"""The Pallas flash prefill vs the portable XLA reference implementation.

Runs the real kernel code in Pallas interpreter mode on CPU (the TPU
compiles the same kernel), checking numerics, GQA head grouping, causal
masking, gradients through the custom VJP, and an end-to-end engine
generation on the pallas path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.ops import attention
from distributed_llm_tpu.ops.pallas_attention import flash_causal_attention


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize("b,s,nq,nkv,d", [
    (1, 64, 4, 4, 16),        # MHA
    (2, 128, 4, 2, 32),       # GQA, multiple batch
    (1, 256, 8, 2, 16),       # more blocks than one (bq=128)
])
def test_flash_causal_matches_xla(b, s, nq, nkv, d):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (_rand(ks[0], (b, s, nq, d)), _rand(ks[1], (b, s, nkv, d)),
               _rand(ks[2], (b, s, nkv, d)))
    got = flash_causal_attention(q, k, v)
    want = attention.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_causal_is_causal():
    # Perturbing future positions must not change earlier outputs.
    b, s, n, d = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand(ks[0], (b, s, n, d)), _rand(ks[1], (b, s, n, d)),
               _rand(ks[2], (b, s, n, d)))
    base = flash_causal_attention(q, k, v)
    k2 = k.at[:, s // 2:].set(99.0)
    v2 = v.at[:, s // 2:].set(-99.0)
    pert = flash_causal_attention(q, k2, v2)
    np.testing.assert_allclose(np.asarray(base[:, :s // 2]),
                               np.asarray(pert[:, :s // 2]), atol=1e-6)


def test_flash_causal_grad_matches_xla():
    b, s, nq, nkv, d = 1, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (_rand(ks[0], (b, s, nq, d)), _rand(ks[1], (b, s, nkv, d)),
               _rand(ks[2], (b, s, nkv, d)))

    def loss_flash(q, k, v):
        return jnp.sum(flash_causal_attention(q, k, v) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(attention.causal_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for gf, gx in zip(g_flash, g_xla):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gx),
                                   atol=2e-4, rtol=2e-4)


def test_resolve_impl(monkeypatch):
    assert attention.resolve_impl("xla") == "xla"
    assert attention.resolve_impl("pallas") == "pallas"
    # auto is the GSPMD-safe XLA path; engines opt into pallas explicitly.
    assert attention.resolve_impl("auto") == "xla"
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    assert attention.resolve_impl("xla") == "pallas"    # env wins
    monkeypatch.setenv("DLLM_ATTENTION", "bogus")
    with pytest.raises(ValueError):
        attention.resolve_impl("auto")                  # typo'd kill switch
    monkeypatch.delenv("DLLM_ATTENTION")
    with pytest.raises(ValueError):
        attention.resolve_impl("flash")


def test_flash_rejects_non_divisible_seq():
    q = jnp.zeros((1, 192, 2, 16))
    k = v = jnp.zeros((1, 192, 2, 16))
    with pytest.raises(ValueError, match="not a multiple"):
        flash_causal_attention(q, k, v)


def test_engine_generates_identically_on_pallas_path(monkeypatch):
    """Greedy generation must be token-identical across attention impls
    (same math, same argmax)."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.engine.inference import InferenceEngine

    tier = TierConfig(name="nano", model_preset="nano_test",
                      max_new_tokens=8, prefill_buckets=(16, 32))

    monkeypatch.setenv("DLLM_ATTENTION", "xla")
    r_xla = InferenceEngine(tier, seed=7).generate(
        "hello world", max_new_tokens=6)
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    r_pal = InferenceEngine(tier, seed=7).generate(
        "hello world", max_new_tokens=6)
    assert r_xla.token_ids == r_pal.token_ids


def test_batched_engine_generates_identically_on_pallas_path(monkeypatch):
    """Greedy generation through the batching engine (the flash prefill,
    paged decode + chunked suffix prefill) must be token-identical across
    impls."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine

    tier = TierConfig(name="nano", model_preset="nano_test",
                      max_new_tokens=6, prefill_buckets=(16, 32),
                      decode_batch=2, kv_block_size=16)
    outs = {}
    for impl in ("xla", "pallas"):
        monkeypatch.setenv("DLLM_ATTENTION", impl)
        eng = ContinuousBatchingEngine(tier, seed=9)
        try:
            # Two turns so the second goes through the paged suffix chunk.
            h = [{"role": "user", "content": "tell me about mountains"}]
            r1 = eng.generate(h)
            h += [{"role": "assistant", "content": r1.text},
                  {"role": "user", "content": "now oceans?"}]
            outs[impl] = (r1.token_ids, eng.generate(h).token_ids)
        finally:
            eng.stop()
    assert outs["xla"] == outs["pallas"]


