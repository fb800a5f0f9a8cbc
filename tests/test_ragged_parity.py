"""Ragged paged decode (ISSUE 6): parity pins and engine rewire checks.

The contract under test: the ragged fused decode tick — one
``attention.paged_decode`` call over every slot's FULL block-table row
with true per-slot lengths — produces BYTE-IDENTICAL greedy output to
the dense windowed path it replaces, across skewed lengths, at the
``decode_batch`` boundaries (1 slot / full occupancy), on the int8-KV
pool, and through a mid-decode preemption + replay (the PR 5
interaction).  The op itself is held to a float64 reference in
tests/test_attention_forms.py; the compile-churn tests pin the
one-decode-program property that is the tentpole's point.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from distributed_llm_tpu.config import tiny_batched_cluster
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine

SHORT = "short question about rivers please"
LONG = ("long question: " + "rivers lakes mountains oceans deltas " * 16)


def _tier(**overrides):
    base = dataclasses.replace(tiny_batched_cluster().nano,
                               max_new_tokens=16,
                               enable_prefix_cache=False)
    return dataclasses.replace(base, **overrides)


def _generate_all(tier, prompts, seed=0):
    engine = ContinuousBatchingEngine(tier, seed=seed)
    try:
        reqs = [engine.submit(p) for p in prompts]
        for r in reqs:
            assert r.done.wait(timeout=120)
        for r in reqs:
            if r.error is not None:
                raise r.error
        return [tuple(r.result.token_ids) for r in reqs], engine._compiled
    finally:
        engine.stop()


# -- which tick ------------------------------------------------------------------

def test_dllm_ragged_env_override(monkeypatch):
    monkeypatch.setenv("DLLM_RAGGED", "0")
    eng = ContinuousBatchingEngine(_tier(), seed=0)
    try:
        assert eng.ragged is False
    finally:
        eng.stop()
    monkeypatch.setenv("DLLM_RAGGED", "1")
    eng = ContinuousBatchingEngine(_tier(attention_ragged=False), seed=0)
    try:
        assert eng.ragged is True
    finally:
        eng.stop()
    monkeypatch.setenv("DLLM_RAGGED", "yes")
    with pytest.raises(ValueError, match="DLLM_RAGGED"):
        ContinuousBatchingEngine(_tier(), seed=0)


# -- engine parity: ragged == dense, byte-identical ---------------------------

def test_ragged_matches_dense_skewed_full_occupancy():
    """Mixed short/long prompts at full decode_batch occupancy: the
    ragged fused tick and the dense windowed tick emit identical greedy
    tokens."""
    prompts = [SHORT, LONG, SHORT + " again", LONG + " again",
               SHORT, LONG]                     # > slots: queueing too
    dense, dense_compiled = _generate_all(
        _tier(attention_ragged=False), prompts)
    ragged, ragged_compiled = _generate_all(
        _tier(attention_ragged=True), prompts)
    assert dense == ragged
    # The tentpole property: ONE compiled decode program under ragged;
    # the dense rung ladder needs more as windows cross buckets.
    assert len(ragged_compiled.get("decode", ())) == 1
    assert len(dense_compiled.get("decode", ())) >= 1


def test_ragged_matches_dense_single_slot():
    """decode_batch=1 boundary: a 1-slot batched engine still serves
    through the fused ragged call."""
    tier = _tier(decode_batch=1)
    dense, _ = _generate_all(
        dataclasses.replace(tier, attention_ragged=False), [LONG])
    ragged, _ = _generate_all(
        dataclasses.replace(tier, attention_ragged=True), [LONG])
    assert dense == ragged


def test_ragged_matches_dense_int8_kv():
    """int8 pool boundary: the paged decode op dequantizes
    byte-identically to the dense paged path."""
    tier = _tier(kv_quantize="int8")
    prompts = [SHORT, LONG, SHORT + " more"]
    dense, _ = _generate_all(
        dataclasses.replace(tier, attention_ragged=False), prompts)
    ragged, _ = _generate_all(
        dataclasses.replace(tier, attention_ragged=True), prompts)
    assert dense == ragged


def test_ragged_preempt_replay_byte_identical():
    """PR 5 interaction: a mid-decode preemption + replay on the ragged
    tick resumes byte-identically (the replayed slot's table row changes
    wholesale — the cached full-table upload must be invalidated)."""
    probe_a = "tell me about rivers and lakes and streams and oceans please"
    probe_b = "what is the tallest mountain on the continent of asia today"
    solo = ContinuousBatchingEngine(
        _tier(decode_batch=2, max_new_tokens=24), seed=1)
    try:
        base_a = solo.generate(probe_a).text
        base_b = solo.generate(probe_b).text
        assert solo.ragged is True          # default-on covers the solo runs
    finally:
        solo.stop()
    tight = ContinuousBatchingEngine(
        _tier(decode_batch=2, max_new_tokens=24, kv_pool_blocks=5), seed=1)
    res = {}
    try:
        threads = [threading.Thread(
            target=lambda k, q: res.__setitem__(k, tight.generate(q)),
            args=(k, q)) for k, q in (("a", probe_a), ("b", probe_b))]
        threads[0].start()
        time.sleep(0.02)
        threads[1].start()
        for t in threads:
            t.join(timeout=120)
        assert tight.preempted_total >= 1
        assert res["a"].text == base_a
        assert res["b"].text == base_b
        assert tight.allocator.available == tight.paged.num_blocks - 1
    finally:
        tight.stop()


# -- engine mechanics ---------------------------------------------------------

def test_ragged_tick_reuses_cached_table_upload():
    """Between table mutations the ragged tick reuses ONE device array
    for the full tables (the dense path re-sliced host→device every
    tick); any slot change invalidates the cache."""
    eng = ContinuousBatchingEngine(_tier(), seed=0)
    try:
        real = eng._decode_step()
        seen = []

        def spy(params, pool, tables, pos, cur, temps, rng):
            seen.append(tables)
            return real(params, pool, tables, pos, cur, temps, rng)

        eng._decode_fn = spy
        eng.generate(SHORT, max_new_tokens=12)
        assert len(seen) >= 2
        # Consecutive ticks between mutations hand the SAME array object
        # to the device call — no per-tick re-upload.
        assert any(a is b for a, b in zip(seen, seen[1:])), (
            "every tick re-uploaded the tables")
        # And a table mutation invalidates the cache (the slot release at
        # finish already exercised this path).
        assert eng._tables_dev is None
        eng._tables_dev = object()
        eng._set_table_row(0, eng._table_row([]))
        assert eng._tables_dev is None
    finally:
        eng.stop()


def test_decode_tick_metrics_and_ring():
    """The tick ring fills, and the obs counter attributes ticks to the
    fused tick's kind + what served its attention (here XLA)."""
    from distributed_llm_tpu.obs import get_observability
    m = get_observability().m
    eng = ContinuousBatchingEngine(_tier(), seed=0)
    try:
        before = m.decode_ticks.labels("nano", "ragged_decode", "xla").value
        eng.generate(SHORT, max_new_tokens=8)
        assert len(eng.tick_ms) >= 1
        assert all(t >= 0.0 for t in eng.tick_ms)
        after = m.decode_ticks.labels("nano", "ragged_decode", "xla").value
        assert after > before
        assert m.decode_tick_ms.labels("nano").count >= 1
        # Compiled-program gauge mirrors the engine's churn surface.
        gauge = m.compiled_programs.labels("nano", "decode")
        assert gauge.value >= 1
    finally:
        eng.stop()


def test_ragged_request_is_the_windowed_tick_on_tpu_unless_forced(
        monkeypatch):
    """On a TPU backend attention_ragged=True keeps the windowed tick (the
    fused tick's gather spans the whole table; never measured better on
    the chip), whatever the engine's attention_impl or DLLM_ATTENTION;
    DLLM_RAGGED=1 forces past the rule."""
    eng = ContinuousBatchingEngine(_tier(), seed=0)
    try:
        assert eng._resolve_ragged() is True          # off the TPU: fused
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("DLLM_RAGGED", raising=False)
        eng.cfg = dataclasses.replace(eng.cfg, attention_impl="pallas")
        assert eng._resolve_ragged() is False
        monkeypatch.setenv("DLLM_ATTENTION", "pallas")
        assert eng._resolve_ragged() is False
        monkeypatch.setenv("DLLM_RAGGED", "1")
        assert eng._resolve_ragged() is True
    finally:
        eng.stop()


def test_tp_mesh_engine_ragged_iff_qualifying():
    """PR 16 flipped the mesh rule: a QUALIFYING TP mesh (dense model,
    sp=ep=1, tp dividing both head counts —
    parallel/tp_attention._tp_ragged_ok) runs the fused ragged tick
    under shard_map; a non-qualifying one (here an MoE model, which
    param-shards fine over 'tp' but whose expert dispatch the ragged
    wrap doesn't cover) still keeps the dense windowed path even when
    the tier asks for ragged."""
    eng = ContinuousBatchingEngine(
        _tier(attention_ragged=True), seed=0,
        mesh=jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",)))
    try:
        assert eng.ragged is True
    finally:
        eng.stop()
    eng = ContinuousBatchingEngine(
        _tier(attention_ragged=True, model_preset="moe_test"), seed=0,
        mesh=jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",)))
    try:
        assert eng.ragged is False    # MoE: _tp_ragged_ok rejects experts
    finally:
        eng.stop()
