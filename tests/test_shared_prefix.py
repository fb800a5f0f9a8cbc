"""Cross-request shared-prefix KV (ISSUE 10): refcounted copy-on-write
block sharing over the paged pool.

The contracts under test:

- BlockAllocator refcount invariants: alloc→1, share increfs, free
  decrefs and only refcount-0 blocks return to the free list; double
  free and share-of-freed raise.
- COW boundary isolation: a sharer never observes a writer's suffix —
  ``copy_block`` at the pool level, and byte-identity of N concurrent
  same-prefix sessions against a cold engine at the engine level (the
  sessions' suffixes start mid-block, so the copy path really runs).
- Eviction skips pinned entries; ``reclaimable_blocks`` counts only
  refcount-1 blocks of unpinned entries, so the KV-admission gate never
  promises supply that sharing has pinned.
- Preemption/replay and stop/drain stay byte-identical / leak-free
  under sharing.
- ``TierConfig.share_prefix_kv=False`` restores the exclusive take
  semantics exactly.

All fast and deterministic (greedy decode, fixed seeds).
"""

import dataclasses
import queue
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import tiny_batched_cluster
from distributed_llm_tpu.engine.batching import (ContinuousBatchingEngine,
                                                 EngineStoppedError)
from distributed_llm_tpu.engine.paged_kv import (BlockAllocator, PagedConfig,
                                                 copy_block, init_pool)
from distributed_llm_tpu.engine.prefix_cache import PrefixCache

# ~19 subword tokens on the tiny BPE: parks under the 32 bucket and every
# session suffix below starts MID-block (19 % 16 != 0), so shared hits
# exercise the COW boundary copy, not just whole-block mapping.
SYS = "system: rivers lakes mountains oceans deltas streams"


def _tier(**kw):
    base = dict(max_new_tokens=8)
    base.update(kw)
    return dataclasses.replace(tiny_batched_cluster().nano, **base)


def _session_prompts(k=3):
    return [SYS + f" q{i}?" for i in range(k)]


def _run_concurrent(eng, prompts):
    """Generate all prompts concurrently; returns results in order."""
    res = {}

    def go(i, p):
        res[i] = eng.generate(p)

    threads = [threading.Thread(target=go, args=(i, p), daemon=True)
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert sorted(res) == list(range(len(prompts))), "a session hung"
    return [res[i] for i in range(len(prompts))]


# -- refcount invariants ------------------------------------------------------

def test_refcount_alloc_share_free_invariants():
    a = BlockAllocator(8)                    # blocks 1..7 allocatable
    got = a.alloc(3)
    assert a.available == 4
    assert all(a.refcount(b) == 1 for b in got)
    a.share(got)
    assert all(a.refcount(b) == 2 for b in got)
    # Sharing takes nothing off the free list.
    assert a.available == 4
    a.free(got)                              # one holder remains
    assert a.available == 4
    assert all(a.refcount(b) == 1 for b in got)
    a.free(got)                              # last holder: blocks return
    assert a.available == 7
    assert all(a.refcount(b) == 0 for b in got)
    with pytest.raises(ValueError):
        a.free([got[0]])                     # double free
    with pytest.raises(ValueError):
        a.share([got[0]])                    # share of a freed block
    a.free([0])                              # trash block: always a no-op
    assert a.available == 7


def test_refcount_free_is_all_or_nothing_on_double_free():
    """A free() batch containing a dead block raises BEFORE mutating
    anything — a partial decref would corrupt the survivors' counts."""
    a = BlockAllocator(8)
    got = a.alloc(2)
    a.free([got[0]])
    with pytest.raises(ValueError):
        a.free([got[1], got[0]])             # got[0] already freed
    # got[1] kept its reference (the batch failed whole).
    assert a.refcount(got[1]) == 1
    a.free([got[1]])
    assert a.available == 7


def test_ref_stats_sharing_picture():
    a = BlockAllocator(8)
    got = a.alloc(2)
    a.share([got[0]])
    assert a.ref_stats() == {"allocated_blocks": 2, "total_refs": 3,
                             "shared_blocks": 1}
    # Batch reader (one lock acquisition — the reclaimable-accounting
    # path) agrees with the per-block reader.
    assert a.refcounts(got + [7]) == [2, 1, 0]
    a.free(got)
    a.free([got[0]])
    assert a.ref_stats() == {"allocated_blocks": 0, "total_refs": 0,
                             "shared_blocks": 0}


# -- COW boundary copy (pool level) ------------------------------------------

@pytest.mark.parametrize("kv_quantize", ["none", "int8"])
def test_copy_block_isolates_writer_from_source(kv_quantize):
    cfg = _tier().model()
    pcfg = PagedConfig(block_size=8, max_slots=1, max_seq_len=32)
    pool = init_pool(cfg, pcfg, kv_quantize)
    one = jnp.ones_like(pool["k"][:, 1])
    pool = dict(pool, k=pool["k"].at[:, 1].set(one))
    copied = copy_block(pool, jnp.asarray(1, jnp.int32),
                        jnp.asarray(2, jnp.int32))
    assert bool((copied["k"][:, 2] == one).all())
    if kv_quantize == "int8":
        assert bool((copied["ks"][:, 2] == pool["ks"][:, 1]).all())
    # The writer scribbles over its private copy; the source block (the
    # sharers' view) must not move.
    written = dict(copied, k=copied["k"].at[:, 2].set(7 * one))
    assert bool((written["k"][:, 1] == one).all())


# -- shared hits: byte-identity + no crosstalk -------------------------------

def test_shared_hits_byte_identical_to_cold_and_no_crosstalk():
    """Prime parks the system prompt; three CONCURRENT sessions extend
    it with different suffixes.  All three must take SHARED hits and
    emit exactly the tokens a cold engine (no cache) produces — which
    also proves no sharer observes another's boundary-block writes."""
    prompts = _session_prompts(3)
    eng = ContinuousBatchingEngine(_tier(), seed=3)
    try:
        eng.generate(SYS)                      # prime: parks the prefix
        results = _run_concurrent(eng, prompts)
        st = eng.prefix_cache.stats()
        assert st["hits_shared"] == 3, st
        assert st["hits_exclusive"] == 0, st
        assert st["tokens_saved_shared"] > 0
        assert st["tokens_saved"] == (st["tokens_saved_shared"]
                                      + st["tokens_saved_exclusive"])
    finally:
        eng.stop()
    assert eng.allocator.available == eng.paged.num_blocks - 1

    cold = ContinuousBatchingEngine(_tier(enable_prefix_cache=False), seed=3)
    try:
        for p, r in zip(prompts, results):
            assert cold.generate(p).token_ids == r.token_ids
    finally:
        cold.stop()


def test_shared_hit_skips_reused_prefill_compute():
    """A shared hit must cost only the SUFFIX prefill: the admission
    mints no cold-prefill program beyond the warm set and allocates no
    blocks for the shared region (zero new blocks there)."""
    eng = ContinuousBatchingEngine(_tier(), seed=5)
    try:
        eng.generate(SYS)
        free_before = eng.allocator.available
        rs_before = eng.allocator.ref_stats()
        # Hold the session OPEN (stream) so its slot is resident while
        # we look: once it finishes, put()'s extend-replace collapses
        # the two entries and the sharing picture empties again.
        req = eng.submit(SYS + " q0?", token_queue=queue.Queue())
        assert req.token_queue.get(timeout=120) is not None
        st = eng.prefix_cache.stats()
        assert st["hits_shared"] == 1
        rs_live = eng.allocator.ref_stats()
        # The shared full blocks gained references without allocation:
        # total refs grew by more than physical blocks did.
        assert (rs_live["total_refs"] - rs_before["total_refs"]) \
            > (rs_live["allocated_blocks"] - rs_before["allocated_blocks"])
        # And the session's physical footprint is its private blocks
        # only (boundary copy + suffix + decode room), strictly less
        # than a cold admission's bucket + budget worth.
        cold_need = eng.projected_demand_blocks(SYS + " q0?")
        assert (free_before - eng.allocator.available) < cold_need
        req.done.wait(timeout=120)
        assert req.result is not None and req.result.gen_tokens > 0
    finally:
        eng.stop()


# -- eviction + reclaimable accounting ---------------------------------------

def test_eviction_skips_pinned_entries():
    pc = PrefixCache(capacity=2, min_prefix=2)
    pc.put((1, 2, 3, 4), {"blocks": [1, 2]})
    e, m = pc.share((1, 2, 3, 4, 9))
    assert e is not None and m == 4
    assert pc.pop_oldest() is None           # the only entry is pinned
    pc.put((5, 6, 7, 8), {"blocks": [3]})
    old = pc.pop_oldest()                    # pinned skipped, unpinned out
    assert old is not None and old.ids == (5, 6, 7, 8)
    pc.unpin(e)
    assert pc.pop_oldest() is e


def test_put_replace_and_capacity_skip_pinned():
    evicted = []
    pc = PrefixCache(capacity=1, min_prefix=2, on_evict=evicted.append)
    pc.put((1, 2, 3), {"blocks": [1]})
    e, m = pc.share((1, 2, 3, 4))
    assert m == 3
    # The longer prompt EXTENDS the pinned entry: the replace sweep and
    # the capacity sweep must both leave it parked (over-capacity is
    # tolerated while pins are live).
    pc.put((1, 2, 3, 4), {"blocks": [1, 5]})
    st = pc.stats()
    assert st["entries"] == 2 and st["pinned_entries"] == 1
    assert evicted == []
    pc.unpin(e)
    # Pins dropped: the next put sweeps back to capacity.
    pc.put((9, 9, 9), {"blocks": [7]})
    assert pc.stats()["entries"] == 1
    assert len(evicted) == 2


def test_take_skips_pinned_entries():
    """Exclusive take must never hand out an entry with live sharers —
    the taker would write into the boundary block they still map."""
    pc = PrefixCache(capacity=2, min_prefix=2)
    pc.put((1, 2, 3, 4), {"blocks": [1]})
    e, _ = pc.share((1, 2, 3, 4, 9))
    taken, m = pc.take((1, 2, 3, 4, 9))
    assert taken is None and m == 0
    pc.unpin(e)
    taken, m = pc.take((1, 2, 3, 4, 9))
    assert taken is e and m == 4


def test_unshare_reverses_hit_accounting():
    pc = PrefixCache(capacity=2, min_prefix=2)
    pc.put((1, 2, 3, 4), {"blocks": [1]})
    e, m = pc.share((1, 2, 3, 4, 9))
    pc.unshare(e, m)
    st = pc.stats()
    assert st["hits"] == 0 and st["hits_shared"] == 0
    assert st["tokens_saved_shared"] == 0 and st["misses"] == 1
    assert st["pinned_entries"] == 0


def test_reclaimable_counts_only_refcount1_unpinned_blocks():
    refs = {1: 2, 2: 1, 3: 1}
    pc = PrefixCache(capacity=4, min_prefix=2,
                     block_refcounts=lambda bs: [refs.get(b, 0)
                                                 for b in bs])
    pc.put((1, 2, 3, 4), {"blocks": [1, 2]})   # block 1 shared elsewhere
    assert pc.reclaimable_blocks() == 1
    e, _ = pc.share((1, 2, 3, 4, 9))
    assert pc.reclaimable_blocks() == 0        # pinned entry excluded
    pc.unpin(e)
    assert pc.reclaimable_blocks() == 1
    # Without a refcount reader the old whole-entry accounting stands.
    pc2 = PrefixCache(capacity=4, min_prefix=2)
    pc2.put((1, 2, 3, 4), {"blocks": [1, 2]})
    assert pc2.reclaimable_blocks() == 2


def test_admission_supply_never_overpromised_under_sharing():
    """Engine-level: after two shared sessions whose suffixes DIVERGE,
    two parked entries hold references to the SAME physical full
    blocks.  reclaimable_blocks must undercount (refcount-1 only) so
    that free + reclaimable never exceeds what an eviction sweep can
    truly free — the admission gate's supply view stays honest."""
    eng = ContinuousBatchingEngine(_tier(), seed=3)
    try:
        eng.generate(SYS)
        eng.generate(SYS + " q0?")    # parks SYS+q0 (replaces the prime)
        eng.generate(SYS + " q1?")    # diverges: both entries stay parked
        st = eng.kv_stats()
        assert st["shared_blocks"] >= 1          # entries share the prefix
        assert st["dedup_ratio"] > 1.0
        total_parked = sum(
            len(e.cache["blocks"]) for e in eng.prefix_cache._entries)
        assert st["reclaimable_blocks"] < total_parked
        # A full eviction sweep frees AT LEAST what was promised.
        free_before = st["free_blocks"]
        while eng.prefix_cache.pop_oldest() is not None:
            pass
        assert eng.allocator.available \
            >= free_before + st["reclaimable_blocks"]
        assert eng.allocator.available == eng.paged.num_blocks - 1
    finally:
        eng.stop()


# -- resident-KV scaling ------------------------------------------------------

def test_resident_blocks_scale_sublinearly_with_sharers():
    """K=4 concurrent same-prefix sessions resident at once: sharing ON
    must hold strictly fewer physical blocks than sharing OFF (the
    bench ``shared_prefix`` leg pins the <0.6x ratio; this pins the
    direction deterministically).  Long prefix via a wider bucket
    ladder so the shared region dominates the per-session suffix."""
    prefix = ("system: you are a geography assistant. " +
              "rivers lakes mountains oceans deltas streams glaciers " * 3)
    prompts = [prefix + f" q{i}?" for i in range(4)]
    peaks = {}
    for share in (True, False):
        tier = _tier(share_prefix_kv=share, max_new_tokens=6,
                     prefill_buckets=(16, 32, 64, 128))
        eng = ContinuousBatchingEngine(tier, seed=9)
        try:
            eng.generate(prefix)                 # park the prefix
            reqs = [eng.submit(p, token_queue=queue.Queue())
                    for p in prompts]
            # First token on each queue == all four sessions admitted
            # and resident simultaneously (decode_batch is 4).
            for r in reqs:
                assert r.token_queue.get(timeout=120) is not None
            st = eng.kv_stats()
            peaks[share] = st["total_blocks"] - st["free_blocks"]
            if share:
                assert st["shared_blocks"] >= 1
                assert st["pinned_entries"] >= 1
                assert st["dedup_ratio"] > 1.0
            for r in reqs:                       # drain to completion
                r.done.wait(timeout=120)
        finally:
            eng.stop()
    assert peaks[True] < peaks[False], peaks


# -- preemption / replay / stop under sharing --------------------------------

def test_preempt_replay_byte_identical_under_sharing():
    """Two same-prefix sessions on a pool too small for both to grow:
    whatever mix of eviction, COW sharing and preemption-replay the
    scheduler takes, the final texts must equal the roomy-pool runs."""
    prompts = _session_prompts(2)
    roomy = ContinuousBatchingEngine(_tier(decode_batch=2,
                                           max_new_tokens=24), seed=1)
    try:
        roomy.generate(SYS)
        base = [roomy.generate(p).text for p in prompts]
    finally:
        roomy.stop()
    tight = ContinuousBatchingEngine(
        _tier(decode_batch=2, max_new_tokens=24, kv_pool_blocks=6), seed=1)
    try:
        tight.generate(SYS)
        results = _run_concurrent(tight, prompts)
        assert [r.text for r in results] == base
    finally:
        tight.stop()
    assert tight.allocator.available == tight.paged.num_blocks - 1


def test_stop_under_sharing_frees_every_reference():
    """stop() with live shared sessions mid-stream: every caller gets
    the engine-stopped shape and the pool ends whole (no leaked refs)."""
    eng = ContinuousBatchingEngine(_tier(max_new_tokens=64), seed=3)
    try:
        eng.generate(SYS)
        reqs = [eng.submit(p, token_queue=queue.Queue())
                for p in _session_prompts(3)]
        for r in reqs:
            assert r.token_queue.get(timeout=120) is not None
    finally:
        eng.stop()
    for r in reqs:
        r.done.wait(timeout=10)
        assert r.result is not None or isinstance(r.error,
                                                  EngineStoppedError)
    assert eng.allocator.available == eng.paged.num_blocks - 1
    assert eng.allocator.ref_stats()["allocated_blocks"] == 0


# -- sharing OFF restores exclusive semantics --------------------------------

def test_sharing_off_restores_exclusive_take():
    eng = ContinuousBatchingEngine(_tier(share_prefix_kv=False), seed=3)
    try:
        assert eng.share_prefix is False
        eng.generate(SYS)
        res = _run_concurrent(eng, _session_prompts(2))
        assert all(r.gen_tokens > 0 for r in res)
        st = eng.prefix_cache.stats()
        # At most ONE session can reuse (take removes the entry); no
        # pinning, no shared credit, no block ever multi-referenced.
        assert st["hits_shared"] == 0
        assert st["hits_exclusive"] <= 1
        assert st["tokens_saved_shared"] == 0
        assert st["pinned_entries"] == 0
        assert eng.kv_stats()["shared_blocks"] == 0
        assert eng.kv_stats()["dedup_ratio"] == 1.0
    finally:
        eng.stop()
    assert eng.allocator.available == eng.paged.num_blocks - 1


def test_sharing_off_outputs_match_sharing_on():
    """Flipping share_prefix_kv must not change a single token."""
    prompts = _session_prompts(2)
    texts = {}
    for share in (True, False):
        eng = ContinuousBatchingEngine(_tier(share_prefix_kv=share), seed=3)
        try:
            eng.generate(SYS)
            texts[share] = [r.token_ids
                            for r in _run_concurrent(eng, prompts)]
        finally:
            eng.stop()
    assert texts[True] == texts[False]


# -- observability surfaces ---------------------------------------------------

def test_kv_stats_and_prefix_hit_counter_surfaces():
    from distributed_llm_tpu.obs import get_observability
    m = get_observability().m
    eng = ContinuousBatchingEngine(_tier(), seed=3)
    tname = eng.tier.name
    before = {k: m.prefix_hits.labels(tname, k).value
              for k in ("shared", "exclusive", "miss")}
    try:
        eng.generate(SYS)                      # miss (cold)
        eng.generate(SYS + " q0?")             # shared hit
        st = eng.kv_stats()
        for key in ("shared_blocks", "dedup_ratio", "pinned_entries",
                    "free_blocks", "reclaimable_blocks"):
            assert key in st
        assert m.prefix_hits.labels(tname, "miss").value \
            >= before["miss"] + 1
        assert m.prefix_hits.labels(tname, "shared").value \
            >= before["shared"] + 1
        assert m.prefix_hits.labels(tname, "exclusive").value \
            == before["exclusive"]
        # GET /stats' per-tier assembler carries the same snapshot.
        from distributed_llm_tpu.utils.telemetry import engine_stats
        entry = engine_stats(eng)
        assert "kv" in entry and "shared_blocks" in entry["kv"]
        assert entry["decode_attention"] == eng.decode_attention_form()
        assert entry["prefix_cache"]["tokens_saved_shared"] > 0
    finally:
        eng.stop()


def test_sampler_exports_sharing_gauges():
    """The system-state sampler's gauge map includes the new series (the
    router's collect feeds kv_shared_blocks / kv_dedup_ratio)."""
    from distributed_llm_tpu.obs.sampler import _GAUGE_FIELDS
    fields = dict(_GAUGE_FIELDS)
    assert fields["kv_shared_blocks"] == "kv_shared_blocks_g"
    assert fields["kv_dedup_ratio"] == "kv_dedup_ratio_g"


# -- speculative rollback × sharing (ISSUE 15) -------------------------------

def _spec_tier(**kw):
    return _tier(spec_decode=True, draft_preset="nano_test", **kw)


def test_spec_rollback_on_shared_prefix_byte_identical_no_crosstalk():
    """Rejected-tail frontier rewinds on slots whose PREFIX blocks are
    shared (refcount>1): two concurrent same-prefix sessions speculate
    (the disagreeing draft forces rejections + rollback every round),
    outputs match the non-speculating sharing engine byte-for-byte, no
    crosstalk leaks into the sharer, and every reference drops —
    refcounts conserved (free list full after stop)."""
    import dataclasses as _dc
    prompts = _session_prompts(3)
    base = _tier(decode_batch=3)
    eng_plain = ContinuousBatchingEngine(base, seed=11)
    try:
        eng_plain.generate(SYS + " seed?")        # park the shared prefix
        plain = [tuple(r.token_ids)
                 for r in _run_concurrent(eng_plain, prompts)]
    finally:
        eng_plain.stop()

    eng = ContinuousBatchingEngine(
        _dc.replace(base, spec_decode=True, draft_preset="draft_test"),
        seed=11)
    try:
        eng.generate(SYS + " seed?")
        spec = [tuple(r.token_ids)
                for r in _run_concurrent(eng, prompts)]
        assert eng.spec_stats()["drafted_total"] > 0
        total = eng.paged.num_blocks - 1
        eng.prefix_cache.clear()
        assert eng.allocator.available == total, "leaked references"
        assert eng.allocator.ref_stats()["allocated_blocks"] == 0
    finally:
        eng.stop()
    assert spec == plain


def test_spec_rollback_on_cow_boundary_block():
    """The COW boundary case: the parked prefix ends MID-block (SYS is
    ~19 tokens, 19 % 16 != 0), so every shared speculative slot COW'd
    the boundary at admit — rounds of rejection/rollback must never
    reach the sharer's copy.  Pinned by byte-identity of a FOLLOW-UP
    same-prefix session after the speculating sessions finished (its
    hit maps the original parked blocks: corruption would change its
    output) plus refcount conservation."""
    import dataclasses as _dc
    tier = _dc.replace(_tier(decode_batch=2), spec_decode=True,
                       draft_preset="draft_test")
    eng = ContinuousBatchingEngine(tier, seed=11)
    try:
        eng.generate(SYS + " seed?")             # parks the mid-block prefix
        _run_concurrent(eng, _session_prompts(2))
        follow_spec = tuple(eng.generate(SYS + " follow-up?").token_ids)
    finally:
        eng.stop()
    eng2 = ContinuousBatchingEngine(_tier(decode_batch=2), seed=11)
    try:
        eng2.generate(SYS + " seed?")
        _run_concurrent(eng2, _session_prompts(2))
        follow_plain = tuple(eng2.generate(SYS + " follow-up?").token_ids)
    finally:
        eng2.stop()
    assert follow_spec == follow_plain


def test_spec_tick_cow_protects_externally_shared_frontier_block():
    """The defensive half of the rollback contract, driven directly: a
    block inside a slot's speculative write window with a second holder
    is COW-copied by the pre-round guard — the slot's table swaps to a
    private copy carrying the same bytes, the shared block's content is
    untouched, its refcount drops by exactly the slot's reference, and
    the ledger stays conserved."""
    from distributed_llm_tpu.engine.batching import _Request, _Slot
    eng = ContinuousBatchingEngine(
        _spec_tier(decode_batch=1, max_new_tokens=8,
                   enable_prefix_cache=False), seed=11)
    try:
        blocks = eng.allocator.alloc(2)
        req = _Request(history="x", max_new_tokens=8, temperature=0.0)
        slot = _Slot(request=req, blocks=list(blocks), prompt_len=4,
                     budget=8, temperature=0.0, ttft_ms=0.0,
                     tokens=[1], max_blocks=4, spec=True,
                     gamma=eng.spec_gamma_max)
        eng._slots[0] = slot
        eng._set_table_row(0, eng._table_row(slot.blocks))
        eng._pos[0] = 4                       # write window inside block 0
        shared = slot.blocks[0]
        eng.allocator.share([shared])         # second holder appears
        before = np.asarray(eng.pool["k"][:, shared])

        eng._ensure_spec_private([0], eng.spec_gamma_max)

        assert shared not in slot.blocks, "guard must swap the block out"
        fresh = slot.blocks[0]
        np.testing.assert_array_equal(
            np.asarray(eng.pool["k"][:, shared]), before)
        np.testing.assert_array_equal(
            np.asarray(eng.pool["k"][:, fresh]), before)   # true copy
        assert eng.allocator.refcount(shared) == 1            # ours only
        assert eng.allocator.refcount(fresh) == 1
        # Conservation: slot blocks + our shared ref account for every
        # allocated block.
        eng._slots[0] = None
        eng.allocator.free(slot.blocks)
        eng.allocator.free([shared])
        assert eng.allocator.available == eng.paged.num_blocks - 1
    finally:
        eng.stop()
