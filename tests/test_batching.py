"""Paged KV cache + continuous batching tests.

Key invariant: the batched paged engine must generate token-identical
output to the sequential contiguous-cache engine under greedy decoding —
paging and batching change where K/V live, not the math.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import TierConfig
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.engine.inference import InferenceEngine
from distributed_llm_tpu.engine.manager import EngineManager
from distributed_llm_tpu.engine.paged_kv import (BlockAllocator, PagedConfig,
                                                 TRASH_BLOCK)


def _tier(**kw):
    defaults = dict(name="nano", model_preset="nano_test", max_new_tokens=8,
                    prefill_buckets=(16, 32, 64), decode_batch=2,
                    kv_block_size=16)
    defaults.update(kw)
    return TierConfig(**defaults)


def test_allocator_never_hands_out_trash_block():
    alloc = BlockAllocator(num_blocks=5)
    got = alloc.alloc(4)
    assert got is not None and TRASH_BLOCK not in got
    assert alloc.alloc(1) is None            # exhausted
    alloc.free(got)
    assert alloc.available == 4
    alloc.free([TRASH_BLOCK])                # trash is never returned to pool
    assert alloc.available == 4


def test_paged_config_geometry():
    p = PagedConfig(block_size=16, max_slots=3, max_seq_len=100)
    assert p.blocks_per_slot == 7            # ceil(100/16)
    assert p.num_blocks == 22                # 3*7 + trash


def test_batched_generation_matches_sequential_engine():
    prompt = "user: what is the capital of France?"
    seq = InferenceEngine(_tier(decode_batch=1), seed=11)
    r_seq = seq.generate(prompt, max_new_tokens=6)

    batched = ContinuousBatchingEngine(_tier(), seed=11)
    try:
        r_bat = batched.generate(prompt, max_new_tokens=6)
    finally:
        batched.stop()
    assert r_bat.token_ids == r_seq.token_ids
    assert r_bat.prompt_tokens == r_seq.prompt_tokens
    assert r_bat.ttft_ms > 0 and r_bat.total_ms >= r_bat.ttft_ms


def test_concurrent_requests_share_the_loop_and_free_blocks():
    engine = ContinuousBatchingEngine(_tier(decode_batch=3), seed=3)
    total_blocks = engine.allocator.available
    results = {}

    def worker(i):
        results[i] = engine.generate(f"user: request number {i}",
                                     max_new_tokens=4 + i % 3)

    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(7)]       # more requests than slots
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        engine.stop()

    assert len(results) == 7
    for r in results.values():
        assert r.gen_tokens >= 1
        assert r.text == engine.tokenizer.decode(r.token_ids)
    # Every slot retired → every block back in the free list.
    assert engine.allocator.available == total_blocks


def test_batched_respects_temperature_determinism():
    # Greedy (temp 0) twice -> identical output even through the batcher.
    e1 = ContinuousBatchingEngine(_tier(), seed=5)
    e2 = ContinuousBatchingEngine(_tier(), seed=5)
    try:
        a = e1.generate("user: hello", max_new_tokens=5)
        b = e2.generate("user: hello", max_new_tokens=5)
    finally:
        e1.stop()
        e2.stop()
    assert a.token_ids == b.token_ids


def test_manager_selects_batching_engine_and_stops_it():
    mgr = EngineManager(_tier(), warmup_on_start=False)
    engine = mgr.engine()
    assert isinstance(engine, ContinuousBatchingEngine)
    engine.generate("user: ping", max_new_tokens=2)
    assert engine._thread is not None
    mgr.stop_server()
    assert engine._thread is None            # loop joined
    assert not mgr.is_server_running()


def test_rejects_buckets_not_divisible_by_block_size():
    with pytest.raises(ValueError, match="kv_block_size"):
        ContinuousBatchingEngine(_tier(prefill_buckets=(24,)))


def test_stop_fails_pending_requests_instead_of_hanging():
    engine = ContinuousBatchingEngine(_tier(), seed=9)
    r = engine.submit("user: will never run", max_new_tokens=4)
    engine.stop()
    assert r.done.wait(timeout=5)
    if r.error is not None:
        with pytest.raises(RuntimeError, match="stopped"):
            raise r.error
    # Either it squeaked through before stop or it was failed — never hangs.


def test_decode_error_fails_slot_but_scheduler_survives():
    engine = ContinuousBatchingEngine(_tier(), seed=13)
    try:
        boom = RuntimeError("tick exploded")
        calls = {"n": 0}
        real = engine._decode_step()

        def flaky(*args, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise boom
            return real(*args, **kw)

        engine._decode_fn = flaky
        with pytest.raises(RuntimeError, match="tick exploded"):
            engine.generate("user: first", max_new_tokens=4)
        engine._decode_fn = real
        ok = engine.generate("user: second", max_new_tokens=4)
        assert ok.gen_tokens >= 1            # loop survived the dead tick
    finally:
        engine.stop()


def test_mesh_engine_shards_params_and_pool():
    """A mesh-sharded batching engine places params by the Megatron rules
    and the pool on its (merged) kv-head axis (kv_pool_specs)."""
    devs = np.array(jax.devices()[:2])
    mesh = jax.sharding.Mesh(devs, ("tp",))
    eng = ContinuousBatchingEngine(_tier(), mesh=mesh)
    try:
        assert eng.pool["k"].sharding.spec[3] == "tp"
        # Column-parallel Q projection shards its output features.
        assert eng.params["layers"]["wq"].sharding.spec[2] == "tp"
    finally:
        eng.stop()


@pytest.mark.parametrize("quantize,draft", [("none", None),
                                            ("int8", None),
                                            ("none", "draft_test")],
                         ids=["bf16", "int8-weights", "speculative"])
def test_unsharded_engine_lives_on_the_device_it_is_given(quantize, draft):
    """An unsharded batched engine is committed to ``devices[0]`` — the
    chip its tier was carved — not to the process's default device:
    weights, pool (and a draft's) sit there, stay there through a
    multi-turn exchange, and the tokens are the default-device engine's.
    (Before PR 22 it ignored ``devices`` and every tier of a multi-chip
    host stacked up on chip 0.)"""
    tier = _tier(quantize=quantize, draft_preset=draft,
                 spec_decode=True if draft else None)
    home = jax.devices()[3]
    turns = ["user: what is the capital of France?",
             "user: what is the capital of France? assistant: Paris. "
             "user: and of Spain?"]

    def run(devices):
        eng = ContinuousBatchingEngine(tier, seed=5, devices=devices)
        try:
            out = [eng.generate(t, max_new_tokens=6).token_ids
                   for t in turns]
            where = {d for label in ("params", "pool", "params_d", "pool_d")
                     for leaf in jax.tree_util.tree_leaves(
                         getattr(eng, label, None))
                     for d in leaf.devices()}
            return out, where
        finally:
            eng.stop()

    base, _ = run(None)
    placed, where = run([home])
    assert where == {home}
    assert placed == base


def test_multi_step_tick_respects_budget_and_matches_single_step():
    """T decode steps per device call must not change outputs: budgets are
    enforced on host (overshoot discarded) and greedy tokens are identical
    to a 1-step-per-tick engine."""
    one = ContinuousBatchingEngine(_tier(decode_steps_per_tick=1), seed=21)
    multi = ContinuousBatchingEngine(_tier(decode_steps_per_tick=4), seed=21)
    try:
        for budget in (2, 5, 8):             # not multiples of T=4
            q = f"user: count some things please {budget}"
            r1 = one.generate(q, max_new_tokens=budget)
            r4 = multi.generate(q, max_new_tokens=budget)
            assert r1.token_ids == r4.token_ids, (budget, r1, r4)
            assert r4.gen_tokens <= budget
    finally:
        one.stop()
        multi.stop()


def test_multi_step_tick_concurrent_requests_complete():
    engine = ContinuousBatchingEngine(
        _tier(decode_batch=3, decode_steps_per_tick=4), seed=22)
    try:
        reqs = [engine.submit(f"user: question number {i}", max_new_tokens=6)
                for i in range(6)]
        for r in reqs:
            assert r.done.wait(timeout=120)
            assert r.error is None
            assert 1 <= r.result.gen_tokens <= 6
    finally:
        engine.stop()


def test_batched_prefix_reuse_multiturn_matches_cold_sequential():
    """Multi-turn through the batching engine must reuse parked prompt
    blocks (hits > 0) and stay token-identical to a cold sequential
    engine — paging + reuse change where K/V live, not the math."""
    import dataclasses

    tier = _tier(decode_batch=2, prefill_buckets=(32, 64, 128, 256))
    batched = ContinuousBatchingEngine(tier, seed=31)
    cold = InferenceEngine(
        dataclasses.replace(tier, enable_prefix_cache=False), seed=31)
    try:
        history = [{"role": "user", "content": "tell me about rivers"}]
        for turn in range(3):
            rb = batched.generate(history)
            rc = cold.generate(history)
            assert rb.token_ids == rc.token_ids, (turn, rb, rc)
            history = history + [
                {"role": "assistant", "content": rb.text or "ok"},
                {"role": "user", "content": f"more please {turn}"}]
        st = batched.prefix_cache.stats()
        assert st["hits"] >= 2, st
    finally:
        batched.stop()


def test_batched_prefix_reuse_evicts_under_pool_pressure():
    """Parked entries must never starve admissions: when the allocator
    runs dry, LRU parked blocks are reclaimed and every request
    completes."""
    tier = _tier(decode_batch=2, prefill_buckets=(32, 64),
                 prefix_cache_entries=4)
    engine = ContinuousBatchingEngine(tier, seed=33)
    try:
        # Fill the store with distinct prompts (each parks blocks)...
        for i in range(4):
            engine.generate(f"user: unique warm prompt number {i} padded out",
                            max_new_tokens=3)
        assert engine.prefix_cache.stats()["entries"] >= 1
        # ...then flood with concurrent requests needing all pool blocks.
        reqs = [engine.submit(f"user: flood question {i} with extra words",
                              max_new_tokens=6) for i in range(5)]
        for r in reqs:
            assert r.done.wait(timeout=120)
            assert r.error is None and r.result.gen_tokens >= 1
    finally:
        engine.stop()


def test_batched_prefix_park_returns_trailing_blocks():
    """After a clean finish the slot's generation-only blocks return to
    the allocator; only ceil(prompt/bs) blocks stay parked."""
    tier = _tier(decode_batch=1, prefill_buckets=(32, 64),
                 max_new_tokens=8)
    engine = ContinuousBatchingEngine(tier, seed=35)
    try:
        total = engine.allocator.available
        engine.generate("user: " + "a" * 40, max_new_tokens=8)  # 47+1 ids
        parked = engine.prefix_cache.stats()["entries"]
        assert parked == 1
        held = total - engine.allocator.available
        bs = engine.paged.block_size
        assert held == -(-48 // bs), held    # ceil(prompt/bs) blocks only
    finally:
        engine.stop()


def test_batched_tp_mesh_matches_unsharded_tokens():
    """Mesh-sharded continuous batching: the tp=4 engine must produce the
    same greedy tokens as the unsharded batched engine — tensor-parallel
    sharding of params and the paged pool changes where math runs, not
    what it computes."""
    from distributed_llm_tpu.parallel.mesh import tp_mesh

    tier = _tier(name="orin", model_preset="orin_test", decode_batch=3)
    plain = ContinuousBatchingEngine(tier, seed=11)
    tp = ContinuousBatchingEngine(tier, seed=11,
                                  mesh=tp_mesh(jax.devices(), 4))
    try:
        prompts = [f"user: mesh question number {i}?" for i in range(5)]
        a = [plain.generate(p, max_new_tokens=6).token_ids for p in prompts]
        b = [tp.generate(p, max_new_tokens=6).token_ids for p in prompts]
        assert a == b
        # Pool really is sharded over the mesh, on the kv-head axis.
        shard_spec = tp.pool["k"].sharding.spec
        assert shard_spec[3] == "tp", shard_spec
    finally:
        plain.stop()
        tp.stop()


def test_manager_builds_batched_engine_for_sharded_tier():
    """decode_batch>1 on a mesh tier now gets continuous batching (it fell
    back to the sequential engine before mesh support)."""
    from distributed_llm_tpu.parallel.mesh import tp_mesh

    tier = _tier(name="orin", model_preset="orin_test", decode_batch=2)
    mgr = EngineManager(tier, mesh=tp_mesh(jax.devices(), 4),
                        warmup_on_start=False)
    try:
        mgr.start_server()
        assert isinstance(mgr.engine(), ContinuousBatchingEngine)
        res = mgr.engine().generate("user: hello?", max_new_tokens=4)
        assert res.gen_tokens >= 1
    finally:
        mgr.stop_server()


def test_batched_tp_mesh_prefix_reuse_multiturn():
    """Session KV prefix reuse works under the tensor-parallel batching
    engine: the follow-up turn reclaims parked pool blocks and still
    matches the unsharded engine's greedy tokens."""
    from distributed_llm_tpu.parallel.mesh import tp_mesh

    tier = _tier(name="orin", model_preset="orin_test", decode_batch=2,
                 max_new_tokens=6)
    plain = ContinuousBatchingEngine(tier, seed=51)
    tp = ContinuousBatchingEngine(tier, seed=51,
                                  mesh=tp_mesh(jax.devices(), 4))
    try:
        outs = []
        for eng in (plain, tp):
            h = [{"role": "user", "content": "tell me about rivers"}]
            r1 = eng.generate(h)
            h += [{"role": "assistant", "content": r1.text},
                  {"role": "user", "content": "and lakes?"}]
            r2 = eng.generate(h)
            outs.append((r1.token_ids, r2.token_ids))
            assert eng.prefix_cache.stats()["hits"] >= 1
        assert outs[0] == outs[1]
    finally:
        plain.stop()
        tp.stop()
