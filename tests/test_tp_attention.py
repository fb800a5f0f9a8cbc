"""Shard-mapped flash prefill on tensor-parallel meshes (sharded tiers
previously never took the Pallas path).

The flash kernel runs per head-shard under shard_map with zero added
collectives; these tests force the Pallas preference with
DLLM_ATTENTION=pallas (CPU backend would otherwise decline) and assert
token equality with the unsharded engine — sharding moves the math, it
must not change it.
"""

import dataclasses

import jax

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS, tiny_cluster
from distributed_llm_tpu.parallel.mesh import sp_tp_mesh, tp_mesh
from distributed_llm_tpu.parallel.tp_attention import (tp_flash_causal,
                                                       tp_prefill_attn)


def _tier(**kw):
    return dataclasses.replace(tiny_cluster().orin, tp=4, **kw)


def test_tp_flash_matches_xla_attention():
    from distributed_llm_tpu.ops.attention import causal_attention
    mesh = tp_mesh(jax.devices(), 4)
    cfg = MODEL_PRESETS["orin_test"]          # 8 q heads, 4 kv heads
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 32, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    k = jax.random.normal(key, (2, 32, cfg.num_kv_heads, cfg.head_dim),
                          jnp.bfloat16)
    v = jax.random.normal(key, (2, 32, cfg.num_kv_heads, cfg.head_dim),
                          jnp.bfloat16)
    got = jax.jit(tp_flash_causal(mesh))(q, k, v)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_policy_gates(monkeypatch):
    cfg = MODEL_PRESETS["orin_test"]
    mesh = tp_mesh(jax.devices(), 4)
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    assert tp_prefill_attn(mesh, cfg, 64) is not None
    # Explicit xla override wins.
    monkeypatch.setenv("DLLM_ATTENTION", "xla")
    assert tp_prefill_attn(mesh, cfg, 64) is None
    monkeypatch.delenv("DLLM_ATTENTION")
    # CPU backend without the override: declined.
    assert tp_prefill_attn(mesh, cfg, 64) is None
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    # sp meshes belong to ring attention.
    assert tp_prefill_attn(sp_tp_mesh(jax.devices(), sp=4, tp=1),
                           cfg, 64) is None
    # MoE models: hook unsupported.
    assert tp_prefill_attn(mesh, MODEL_PRESETS["moe_test"], 64) is None
    # kv heads must divide.
    assert tp_prefill_attn(mesh, MODEL_PRESETS["nano_test"], 64) is None
    # No mesh: the unsharded upgrade path owns this case.
    assert tp_prefill_attn(None, cfg, 64) is None


def test_tp_engine_with_pallas_prefill_matches_unsharded(monkeypatch):
    """Full TP engine under forced Pallas: the shard-mapped flash
    prefill hook is live (decode stays on the GSPMD XLA path), and
    tokens must match unsharded."""
    from distributed_llm_tpu.engine.inference import InferenceEngine
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    plain = InferenceEngine(_tier(), seed=9)
    tp = InferenceEngine(_tier(), seed=9, mesh=tp_mesh(jax.devices(), 4))
    prompt = "user: does sharded flash prefill match?"
    a = plain.generate(prompt, max_new_tokens=6)
    b = tp.generate(prompt, max_new_tokens=6)
    assert a.token_ids == b.token_ids


def test_tp_batched_int8_pool_pallas_prefill_matches(monkeypatch):
    """TP batching engine with int8 KV under forced Pallas (the
    shard-mapped flash prefill; the fused tick's hook dequantizes its
    shard's gathered rows) still matches the unsharded engine."""
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    tier = _tier(decode_batch=2, max_new_tokens=6, kv_quantize="int8")
    plain = ContinuousBatchingEngine(tier, seed=31)
    tp = ContinuousBatchingEngine(tier, seed=31,
                                  mesh=tp_mesh(jax.devices(), 4))
    try:
        a = plain.generate("user: q8 paged under tp?").token_ids
        b = tp.generate("user: q8 paged under tp?").token_ids
        assert a == b
    finally:
        plain.stop()
        tp.stop()


def test_tp_batched_engine_with_pallas_prefill_matches(monkeypatch):
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    tier = _tier(decode_batch=2, max_new_tokens=6)
    plain = ContinuousBatchingEngine(tier, seed=13)
    tp = ContinuousBatchingEngine(tier, seed=13,
                                  mesh=tp_mesh(jax.devices(), 4))
    try:
        a = plain.generate("user: paged pallas prefill?").token_ids
        b = tp.generate("user: paged pallas prefill?").token_ids
        assert a == b
    finally:
        plain.stop()
        tp.stop()
