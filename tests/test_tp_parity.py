"""Tensor-parallel serving byte-identity (ISSUE 16).

The contract under test: a batched engine on a qualifying tp=2 mesh —
params and the paged KV pool sharded over the kv-head axis, the fused
ragged decode/verify ticks running UNDER shard_map
(parallel/tp_attention.tp_ragged_decode_attn / tp_ragged_verify_attn)
— produces BYTE-IDENTICAL greedy output to the unsharded tp=1 engine
across the whole interaction matrix: shared-prefix COW boundaries,
mid-decode preemption + replay, disaggregated chunked prefill, host-KV
demote/promote, and speculative rounds with a disagreeing draft.  Plus
the perf pin that is the tentpole's point: ONE decode program per
engine at tp>1 (sharding must not reopen the rung ladder).

CPU host devices (--xla_force_host_platform_device_count, set in
conftest) stand in for chips: sharding moves the math, never changes
it, so parity here certifies the wiring the TPU run inherits.
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

SYS = ("system: you are a helpful assistant that answers questions about "
       "rivers lakes and mountains in short sentences")


def _tier(**kw):
    base = dict(max_new_tokens=12, enable_prefix_cache=False)
    base.update(kw)
    return dataclasses.replace(tiny_batched_cluster().nano, **base)


def _mesh(tp):
    if tp == 1:
        return None
    devs = jax.devices()
    if len(devs) < tp:
        pytest.skip(f"needs {tp} host devices")
    return jax.sharding.Mesh(np.array(devs[:tp]), ("tp",))


def _drain(eng, prompts):
    reqs = [eng.submit(p) for p in prompts]
    for r in reqs:
        assert r.done.wait(timeout=180)
    for r in reqs:
        if r.error is not None:
            raise r.error
    return [tuple(r.result.token_ids) for r in reqs]


def _outputs(tier, tp, prompts, seed=0):
    eng = ContinuousBatchingEngine(tier, seed=seed, mesh=_mesh(tp))
    try:
        if tp > 1:
            assert eng.ragged is True, "tp mesh must keep the fused tick"
        return _drain(eng, prompts), dict(eng._compiled)
    finally:
        eng.stop()


PROMPTS = ["short question about rivers please",
           "long question: " + "rivers lakes mountains oceans deltas " * 8,
           "what is the tallest mountain on the continent of asia today"]


# -- basic parity + the one-program pin ---------------------------------------

def test_tp2_greedy_byte_identical_and_one_decode_program():
    base, _ = _outputs(_tier(), 1, PROMPTS)
    tp2, compiled = _outputs(_tier(), 2, PROMPTS)
    assert tp2 == base
    # The tentpole's perf property: sharding must not reopen the dense
    # rung ladder — ONE ragged decode program serves the engine's life.
    assert len(compiled.get("decode", ())) == 1


def test_tp1_mesh_is_byte_identical_to_no_mesh():
    """tp=1 is the byte-identical pre-change default: a ('tp',)-mesh of
    one device and no mesh at all produce the same tokens."""
    base, _ = _outputs(_tier(), 1, PROMPTS[:2])
    one = ContinuousBatchingEngine(
        _tier(), seed=0,
        mesh=jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",)))
    try:
        assert _drain(one, PROMPTS[:2]) == base
    finally:
        one.stop()


# -- interaction matrix -------------------------------------------------------

def test_tp2_shared_prefix_cow_boundary():
    """Concurrent sessions extending a parked prefix take shared hits
    at tp=2; COW boundary-block isolation must hold per shard (the
    block tables are replicated; only KV payloads are sharded)."""
    prompts = [SYS + f" q{i}?" for i in range(3)]

    def run(tp):
        eng = ContinuousBatchingEngine(
            _tier(enable_prefix_cache=True), seed=3, mesh=_mesh(tp))
        try:
            eng.generate(SYS)                  # prime: parks the prefix
            out = _drain(eng, prompts)
            st = eng.prefix_cache.stats()
            assert st["hits_shared"] == 3, st
            return out
        finally:
            eng.stop()

    assert run(2) == run(1)


def test_tp2_preemption_replay_byte_identical():
    """A mid-decode preemption + replay on the sharded ragged tick
    resumes byte-identically — _rewind_frontier/COW rollback operate on
    the replicated block tables, so every shard replays the same row."""
    base, _ = _outputs(_tier(decode_batch=2, max_new_tokens=24), 1,
                       [PROMPTS[0], PROMPTS[2]])
    tight = ContinuousBatchingEngine(
        _tier(decode_batch=2, max_new_tokens=24, kv_pool_blocks=5),
        seed=0, mesh=_mesh(2))
    res = {}
    try:
        threads = [threading.Thread(
            target=lambda k, q: res.__setitem__(k, tight.generate(q)),
            args=(k, q))
            for k, q in (("a", PROMPTS[0]), ("b", PROMPTS[2]))]
        threads[0].start()
        time.sleep(0.02)
        threads[1].start()
        for t in threads:
            t.join(timeout=180)
        assert tight.preempted_total >= 1
        assert [tuple(res["a"].token_ids),
                tuple(res["b"].token_ids)] == base
    finally:
        tight.stop()


def test_tp2_chunked_prefill_byte_identical():
    kw = dict(prefill_chunk_tokens=32, prefill_buckets=(16, 32, 64, 128),
              max_new_tokens=12)
    base, _ = _outputs(_tier(**kw), 1, PROMPTS)
    tp2, _ = _outputs(_tier(**kw), 2, PROMPTS)
    assert tp2 == base


def test_tp2_host_kv_promotion_byte_identical():
    """park → evict(demote to host RAM) → hit(promote) round-trips the
    SHARDED pool's blocks through the host tier byte-identically."""
    prompt = "user: tell me about rivers lakes mountains oceans and deltas"
    turn2 = prompt + " and also glaciers please"
    kw = dict(max_new_tokens=6, decode_batch=2, prefill_chunk_tokens=16,
              enable_prefix_cache=True, prefix_cache_entries=4,
              host_kv_bytes=64 * 1024 * 1024)

    def run(tp):
        eng = ContinuousBatchingEngine(_tier(**kw), seed=11, mesh=_mesh(tp))
        try:
            r1 = eng.generate(prompt)
            assert eng.prefix_cache.pop_oldest() is not None
            assert eng.kv_spill.flush(10.0)
            assert eng.kv_spill.stats()["demotions_total"] == 1
            r2 = eng.generate(turn2)
            assert eng.kv_spill.stats()["promotions_total"] == 1
            return [tuple(r1.token_ids), tuple(r2.token_ids)]
        finally:
            eng.stop()

    assert run(2) == run(1)


def test_tp2_spec_round_disagreeing_draft():
    """Speculative rounds survive sharding: the draft stays REPLICATED
    (each chip drafts the full problem locally) while the verify is ONE
    fused sharded call; a disagreeing draft (different architecture)
    exercises rejection + rewind on the replicated tables."""
    spec = _tier(spec_decode=True, draft_preset="draft_test")
    base, _ = _outputs(spec, 1, PROMPTS)
    eng = ContinuousBatchingEngine(spec, seed=0, mesh=_mesh(2))
    try:
        assert eng.spec, "spec must arm on the qualifying tp mesh"
        out = _drain(eng, PROMPTS)
        st = eng.spec_stats()
        assert st["enabled"] and st["drafted_total"] > 0
        # Drafted tokens land: speculation is a win, not a no-op.
        assert st["accepted_total"] > 0
        compiled = dict(eng._compiled)
    finally:
        eng.stop()
    assert out == base
    plain, _ = _outputs(_tier(), 1, PROMPTS)
    assert out == plain
    # Draft/verify program families are keyed by (γ_bucket, span, tp) —
    # every minted key must carry this engine's tp degree.
    for stage in ("draft", "verify"):
        assert compiled.get(stage), stage
        for key in compiled[stage]:
            if stage == "draft" and isinstance(key[0], str):
                continue      # draft prefill/writer/chunk sub-keys
            assert key[-1] == 2, (stage, key)


def test_tp2_self_draft_accepts_everything():
    """Self-draft at tp=2: the draft shares the target's sharded params
    and pool, so its greedy continuation IS the target's — acceptance
    pins at 1.0 exactly as unsharded."""
    spec = _tier(spec_decode=True, draft_preset="nano_test")
    eng = ContinuousBatchingEngine(spec, seed=0, mesh=_mesh(2))
    try:
        out = _drain(eng, PROMPTS[:2])
        st = eng.spec_stats()
        assert st["accept_ratio"] == 1.0
    finally:
        eng.stop()
    base, _ = _outputs(_tier(), 1, PROMPTS[:2])
    assert out == base


# -- capacity is enforced, not discovered --------------------------------------

@pytest.mark.parametrize("tp", [1, 2])
def test_hbm_budget_refuses_at_tp1_and_serves_at_tp2(tp):
    """``TierConfig.hbm_gb_per_chip`` set between the two per-chip
    footprints: the unsharded tier is refused by ``start_server`` before
    a weight exists (TierOverCapacityError, no engine), the tp=2 tier of
    the same model fits and serves.  mini_bench, because nano_test's
    footprint vanishes under the budget's rounding."""
    from distributed_llm_tpu.engine.manager import (EngineManager,
                                                    TierOverCapacityError)
    from distributed_llm_tpu.utils.hbm_budget import tier_hbm_budget

    mesh2 = _mesh(2)
    base = dataclasses.replace(
        _tier(max_new_tokens=4), model_preset="mini_bench", decode_batch=2,
        prefill_buckets=(16, 32, 64))
    b1 = tier_hbm_budget(base)["total_gb_per_chip"]
    b2 = tier_hbm_budget(dataclasses.replace(base, tp=2),
                         mesh=mesh2)["total_gb_per_chip"]
    assert b2 < b1
    budget = (b1 + b2) / 2 + 0.75           # + the activation headroom
    tier = dataclasses.replace(base, tp=tp, hbm_gb_per_chip=budget)
    mgr = EngineManager(tier, mesh=mesh2 if tp == 2 else None,
                        devices=None if tp == 2 else jax.devices()[:1],
                        warmup_on_start=False)
    try:
        if tp == 1:
            with pytest.raises(TierOverCapacityError, match="raise tp"):
                mgr.start_server()
            assert mgr._engine is None and not mgr.is_server_running()
        else:
            mgr.start_server()
            assert mgr.engine().generate("hello rivers").token_ids
    finally:
        mgr.stop_server()
