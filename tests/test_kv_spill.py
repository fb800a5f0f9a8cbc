"""Hierarchical KV spill tier (ISSUE 14, engine/kv_spill.py): eviction
from the device prefix cache DEMOTES unpinned sole-owner entries to a
budgeted host-RAM LRU (async copy off the tick path), and a later
prefix hit PROMOTES them back through the chunked-prefill lane — with a
byte-identical cold-prefill fallback whenever promotion loses the race.

The race matrix this file pins (the ISSUE 14 satellite):

- hit-during-demotion: a claim on a still-COPYING entry waits the
  copier out, then promotes byte-identically;
- demotion-during-take: take/share and demotion cannot race by
  construction (eviction removes the entry under the cache lock before
  on_evict fires), and shared-refcount data never demotes;
- promotion-loses (entry invalidated mid-flight) → cold prefill with
  byte-identical output, counted as a promotion race;
- promotion vs concurrent stop/drain: the pin is released, the request
  fails with the engine-stopped shape (stop) or the copier is waited
  out (drain/stop flush);
- host-LRU eviction never drops an entry with a promotion in flight.

Throughput claims belong to the chip benchmark (benchmark/); these
are fast deterministic tests (the copier pause/resume hook makes the
races schedulable instead of probabilistic).
"""

import dataclasses
import threading
import time

import numpy as np

from distributed_llm_tpu.config import tiny_cluster
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.engine.kv_spill import (COPYING, DEAD, RESIDENT,
                                                 HostKVSpill)

PROMPT = "user: tell me about rivers lakes mountains oceans and deltas"
TURN2 = PROMPT + " and also glaciers please"


def _tier(**kw):
    defaults = dict(max_new_tokens=6, decode_batch=2,
                    prefill_chunk_tokens=16, prefix_cache_entries=4,
                    host_kv_bytes=64 * 1024 * 1024)
    defaults.update(kw)
    return dataclasses.replace(tiny_cluster().nano, **defaults)


def _engine(**kw):
    return ContinuousBatchingEngine(_tier(**kw), seed=11)


def _cold_reference(prompts, **kw):
    """Greedy outputs of a spill-less engine over the same prompts —
    the byte-identity oracle for every fallback path."""
    kw.setdefault("host_kv_bytes", None)
    eng = _engine(**kw)
    try:
        return [eng.generate(p).token_ids for p in prompts]
    finally:
        eng.stop()


def _demote_parked(eng, timeout=10.0):
    """Evict the (single) parked prefix and wait for its host copy."""
    assert eng.prefix_cache.pop_oldest() is not None
    assert eng.kv_spill.flush(timeout)


# -- construction gates ------------------------------------------------------

def test_spill_requires_chunked_prefill_and_budget():
    assert _engine(host_kv_bytes=None).kv_spill is None
    assert _engine(host_kv_bytes=0).kv_spill is None
    # No chunk machinery to ride: the spill tier stands down (warned).
    assert _engine(prefill_chunk_tokens=None).kv_spill is None
    assert _engine().kv_spill is not None


def test_env_override_wins(monkeypatch):
    monkeypatch.setenv("DLLM_HOST_KV_BYTES", "0")
    assert _engine().kv_spill is None
    monkeypatch.setenv("DLLM_HOST_KV_BYTES", str(1 << 20))
    eng = _engine(host_kv_bytes=None)
    assert eng.kv_spill is not None
    assert eng.kv_spill.budget_bytes == 1 << 20


# -- demote → promote lifecycle ----------------------------------------------

def test_demote_on_eviction_then_promote_byte_identical():
    """The headline lifecycle: park → evict(demote) → hit(promote),
    outputs byte-identical to a spill-less engine, blocks conserved."""
    ref = _cold_reference([PROMPT, TURN2])
    eng = _engine()
    try:
        r1 = eng.generate(PROMPT)
        assert r1.token_ids == ref[0]
        _demote_parked(eng)
        ss = eng.kv_spill.stats()
        assert ss["demotions_total"] == 1
        assert ss["resident_entries"] == 1 and ss["blocks"] > 0
        assert ss["bytes"] == ss["blocks"] * eng._spill_block_bytes
        r2 = eng.generate(TURN2)
        assert r2.token_ids == ref[1]
        ss = eng.kv_spill.stats()
        assert ss["promotions_total"] == 1
        assert ss["promotion_races_total"] == 0
        assert ss["pinned_entries"] == 0      # promotion unpinned
    finally:
        eng.stop()
    # Every pool block is home (parked entries were cleared by stop).
    assert eng.allocator.available == eng.paged.num_blocks - 1


def test_shared_refcount_blocks_never_demote():
    """Demotion is refcount-1-only: freeing a shared block is just a
    decref (the data stays resident elsewhere), so spilling a second
    copy would waste host budget — the eviction falls through to the
    plain free."""
    eng = _engine()
    try:
        eng.generate(PROMPT)
        entry = eng.prefix_cache._entries[0]
        blocks = entry.cache["blocks"]
        eng.allocator.share(blocks)           # a second holder appears
        assert eng.prefix_cache.pop_oldest() is not None
        assert eng.kv_spill.stats()["entries"] == 0
        # The cache's reference dropped; ours remains.
        assert all(r == 1 for r in eng.allocator.refcounts(blocks))
        eng.allocator.free(blocks)
    finally:
        eng.stop()


def test_budget_too_small_skips_demotion():
    eng = _engine(host_kv_bytes=1)            # can't hold any entry
    try:
        eng.generate(PROMPT)
        free0 = eng.allocator.available
        assert eng.prefix_cache.pop_oldest() is not None
        assert eng.kv_spill.stats()["entries"] == 0
        assert eng.allocator.available > free0   # plain free happened
    finally:
        eng.stop()


def test_failed_reservation_destroys_nothing():
    """Regression (ISSUE 19 fix): the store used to kill the resident
    twin (and evict LRU victims) BEFORE discovering the newcomer could
    not fit — a refused demotion that destroyed promotable state.
    Reservation now plans both kill sets first and commits all or
    nothing, so a False offer() leaves every resident entry claimable."""
    tiles = {"k": np.zeros((1, 1, 2, 4, 2), np.float32),
             "v": np.zeros((1, 1, 2, 4, 2), np.float32)}
    nbytes = sum(a.nbytes for a in tiles.values())
    spill = HostKVSpill(budget_bytes=nbytes * 2, block_bytes=nbytes // 2,
                        min_prefix=4, tier="t")
    try:
        assert spill.offer(tuple(range(8)), tiles, nbytes, nb=2)
        assert spill.offer(tuple(range(100, 108)), tiles, nbytes, nb=2)
        assert spill.flush(10)
        pinned = spill.claim(tuple(range(100, 110)))
        assert pinned is not None
        # A longer twin of the first entry, too big to fit: its twin
        # kill alone frees nbytes, and the only other entry is pinned —
        # the offer must be refused with NOTHING destroyed.
        assert not spill.offer(tuple(range(12)), tiles, nbytes * 2, nb=4)
        st = spill.stats()
        assert st["entries"] == 2 and st["demotions_dropped"] == 1
        assert st["evictions_total"] == 0
        still = spill.claim(tuple(range(10)))
        assert still is not None and still[1] == 8
        spill.release(still[0], promoted=True)
        spill.release(pinned[0], promoted=True)
    finally:
        spill.stop()


# -- the race matrix ---------------------------------------------------------

def test_hit_during_demotion_waits_out_the_copier():
    """A prompt hitting an entry whose demote copy is still in flight
    claims it anyway; the promotion stalls until the copier lands, then
    completes byte-identically (no race, no cold fallback)."""
    ref = _cold_reference([PROMPT, TURN2])
    eng = _engine()
    try:
        assert eng.generate(PROMPT).token_ids == ref[0]
        eng.kv_spill.pause()
        assert eng.prefix_cache.pop_oldest() is not None
        assert eng.kv_spill.stats()["copying_entries"] == 1
        req = eng.submit(TURN2)
        deadline = time.time() + 10
        while (eng.kv_spill.stats()["host_hits"] == 0
               and time.time() < deadline):
            time.sleep(0.001)
        assert eng.kv_spill.stats()["host_hits"] == 1
        assert not req.done.is_set()          # promotion is waiting
        eng.kv_spill.resume()
        assert req.done.wait(timeout=60) and req.error is None
        assert req.result.token_ids == ref[1]
        ss = eng.kv_spill.stats()
        assert ss["promotions_total"] == 1
        assert ss["promotion_races_total"] == 0
    finally:
        eng.kv_spill.resume()
        eng.stop()


def test_promotion_race_falls_back_to_cold_prefill_byte_identical():
    """Entry invalidated mid-promotion (concurrent clear): the claimed
    entry goes DEAD, the promotion aborts, the prefill restarts COLD —
    output byte-identical, race counted, nothing pinned or leaked."""
    ref = _cold_reference([PROMPT, TURN2])
    eng = _engine()
    try:
        assert eng.generate(PROMPT).token_ids == ref[0]
        eng.kv_spill.pause()                  # hold the entry in COPYING
        assert eng.prefix_cache.pop_oldest() is not None
        req = eng.submit(TURN2)
        deadline = time.time() + 10
        while (eng.kv_spill.stats()["host_hits"] == 0
               and time.time() < deadline):
            time.sleep(0.001)
        eng.kv_spill.clear()                  # the race: entry dies
        eng.kv_spill.resume()
        assert req.done.wait(timeout=60) and req.error is None
        assert req.result.token_ids == ref[1]
        ss = eng.kv_spill.stats()
        assert ss["promotion_races_total"] == 1
        assert ss["promotions_total"] == 0
        assert ss["pinned_entries"] == 0
    finally:
        eng.kv_spill.resume()
        eng.stop()
    assert eng.allocator.available == eng.paged.num_blocks - 1


def test_stop_mid_promotion_releases_pin_and_fails_with_shape():
    """Promotion vs concurrent engine stop: the cancel path drops the
    promotion pin and the request fails with the engine-stopped error
    shape (or legally raced to completion)."""
    from distributed_llm_tpu.engine.batching import EngineStoppedError

    eng = _engine()
    try:
        eng.generate(PROMPT)
        eng.kv_spill.pause()
        assert eng.prefix_cache.pop_oldest() is not None
        req = eng.submit(TURN2)
        deadline = time.time() + 10
        while (eng.kv_spill.stats()["host_hits"] == 0
               and time.time() < deadline):
            time.sleep(0.001)
    finally:
        eng.kv_spill.resume()
        eng.stop()
    assert req.done.wait(timeout=10)
    if req.error is not None:                 # raced completion is legal
        assert isinstance(req.error, EngineStoppedError)
        assert "error" in req.error.shape
    assert eng.kv_spill.stats()["pinned_entries"] == 0
    assert eng.allocator.available == eng.paged.num_blocks - 1


def test_host_lru_never_evicts_entry_with_promotion_in_flight():
    """Store-level pin contract: budget pressure evicts unpinned LRU
    entries only — an offer that could only fit by dropping a pinned
    entry is refused instead."""
    tiles = {"k": np.zeros((1, 1, 2, 4, 2), np.float32),
             "v": np.zeros((1, 1, 2, 4, 2), np.float32)}
    nbytes = sum(a.nbytes for a in tiles.values())
    spill = HostKVSpill(budget_bytes=nbytes, block_bytes=nbytes // 2,
                        min_prefix=4, tier="t")
    try:
        assert spill.offer(tuple(range(8)), tiles, nbytes, nb=2)
        assert spill.flush(10)
        claimed = spill.claim(tuple(range(10)))
        assert claimed is not None
        entry, m = claimed
        assert m == 8 and entry.pins == 1
        # A second entry needs the whole budget: the only victim is
        # pinned, so the offer must be refused, not the pin broken.
        assert not spill.offer(tuple(range(100, 108)), tiles, nbytes,
                               nb=2)
        assert spill.stats()["entries"] == 1
        assert spill.entry_state(entry) is RESIDENT
        spill.release(entry, promoted=True)
        # Unpinned now: the same offer evicts it and lands.
        assert spill.offer(tuple(range(100, 108)), tiles, nbytes, nb=2)
        assert spill.flush(10)
        st = spill.stats()
        assert st["entries"] == 1 and st["evictions_total"] == 1
        assert spill.entry_state(entry) is DEAD
    finally:
        spill.stop()


def test_offer_replaces_entries_the_new_one_extends():
    """The device cache's put()-replace rule, host-side: a demotion
    whose ids extend (or duplicate) a parked host entry supersedes it —
    without this the promote → re-park → evict → demote cycle would
    hold a stale shorter copy per session, halving the budget's reach.
    Pinned entries survive (a promotion is reading their buffers)."""
    tiles = {"k": np.zeros((1, 1, 2, 4, 2), np.float32),
             "v": np.zeros((1, 1, 2, 4, 2), np.float32)}
    nbytes = sum(a.nbytes for a in tiles.values())
    spill = HostKVSpill(budget_bytes=nbytes * 8, block_bytes=nbytes // 2,
                        min_prefix=4, tier="t")
    try:
        assert spill.offer(tuple(range(8)), tiles, nbytes, nb=2)
        assert spill.flush(10)
        assert spill.offer(tuple(range(12)), tiles, nbytes, nb=2)
        assert spill.flush(10)
        st = spill.stats()
        assert st["entries"] == 1 and st["bytes"] == nbytes
        claimed = spill.claim(tuple(range(14)))
        assert claimed is not None and claimed[1] == 12   # the longer one
        entry, _ = claimed
        # Pinned: a same-prefix re-demotion must NOT kill the entry a
        # promotion is mid-copy from; the new twin lands beside it.
        assert spill.offer(tuple(range(12)), tiles, nbytes, nb=2)
        assert spill.flush(10)
        assert spill.entry_state(entry) is RESIDENT
        assert spill.stats()["entries"] == 2
        spill.release(entry, promoted=True)
    finally:
        spill.stop()


def test_stop_waits_out_inflight_copies():
    """Drain/stop flushes the copier (bounded): an engine stop issued
    while a demote copy is queued blocks until the copy lands, so the
    host tier is consistent at rest."""
    eng = _engine()
    eng.generate(PROMPT)
    eng.kv_spill.pause()
    assert eng.prefix_cache.pop_oldest() is not None
    assert eng.kv_spill.pending() >= 1
    box = {}

    def stopper():
        eng.stop()
        box["stopped_at"] = time.monotonic()

    t = threading.Thread(target=stopper, daemon=True)
    t.start()
    time.sleep(0.25)
    assert "stopped_at" not in box            # blocked in the flush
    eng.kv_spill.resume()
    t.join(timeout=30)
    assert "stopped_at" in box
    assert eng.kv_spill.stats()["demotions_total"] == 1


def test_demotion_during_take_is_structurally_impossible():
    """take/share and demotion cannot cross: eviction removes the entry
    under the cache lock BEFORE on_evict runs, so a concurrent take
    either won the entry (still parked, no demote) or misses (demoted,
    promotable).  Pin the 'take won' half: a taken entry's blocks are
    the slot's, and the following eviction sweep demotes nothing."""
    eng = _engine()
    try:
        eng.generate(PROMPT)
        entry, m = eng.prefix_cache.take(
            eng.affinity_token_ids(TURN2))
        assert entry is not None and m > 0
        assert eng.prefix_cache.pop_oldest() is None   # cache is empty
        assert eng.kv_spill.stats()["entries"] == 0
        eng.prefix_cache.untake(entry, m)     # restore for cleanup
    finally:
        eng.stop()


# -- integration: churn, stats, affinity -------------------------------------

def test_session_churn_byte_identical_and_warm_hit_rate_improves():
    """Mini spill leg: a session population larger than the device
    cache, revisited — outputs byte-identical spill ON vs OFF, and ON
    converts revisits the device tier lost into promotions."""
    # Session names diverge at token ZERO: a shared opener would let
    # exclusive-mode admissions TAKE the previous session's entry on a
    # trivial common-prefix match, and nothing would ever be evicted
    # (hence demoted) at all.
    names = ("alpha", "bravo", "charlie", "delta")
    prompts = [f"{names[i]} asks about the rivers and lakes of region {i}"
               for i in range(4)]
    revisits = [p + " tell me more" for p in prompts]

    def run(host_bytes, share=True):
        eng = _engine(host_kv_bytes=host_bytes, prefix_cache_entries=1,
                      max_new_tokens=4, share_prefix_kv=share)
        try:
            out = [eng.generate(p).token_ids for p in prompts]
            out += [eng.generate(p).token_ids for p in revisits]
            promoted = (eng.kv_spill.stats()["promotions_total"]
                        if eng.kv_spill is not None else 0)
            return out, promoted
        finally:
            eng.stop()

    off, promoted_off = run(None)
    on, promoted_on = run(64 * 1024 * 1024)
    assert on == off                          # byte-identity under churn
    assert promoted_off == 0
    # With one device-cache slot, at least the non-resident revisits
    # must come back through the host tier.
    assert promoted_on >= 2
    # Exclusive-take mode exercises the untake hand-back when the host
    # match outranks a short cross-session device hit: same bytes.
    excl, promoted_excl = run(64 * 1024 * 1024, share=False)
    assert excl == off
    assert promoted_excl >= 2


def test_kv_stats_surface_and_sampler_gauges():
    """kv_stats carries the host-tier block/byte occupancy and the
    promotion backlog; the sampler mirrors them to the dllm_kv_host_*
    gauges (the /stats + flight-recorder surface of the small fix)."""
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.obs.sampler import SystemStateSampler

    eng = _engine()
    try:
        eng.generate(PROMPT)
        _demote_parked(eng)
        st = eng.kv_stats()
        for key in ("host_entries", "host_blocks", "host_bytes",
                    "host_budget_bytes", "demotions_total",
                    "promotions_total", "promotion_races_total",
                    "demote_inflight", "promote_backlog_blocks"):
            assert key in st, key
        assert st["host_blocks"] > 0 and st["host_bytes"] > 0
        # Spill-less engines keep the historical kv_stats shape.
        off = _engine(host_kv_bytes=None)
        try:
            assert "host_blocks" not in off.kv_stats()
        finally:
            off.stop()
        m = get_observability().m
        sampler = SystemStateSampler(
            lambda: {"nano": {"kv_host_blocks": st["host_blocks"],
                              "kv_host_bytes": st["host_bytes"],
                              "kv_promote_backlog": 3}}, metrics=m)
        sampler.sample_once()
        assert (m.kv_host_blocks_g.labels("nano").value
                == float(st["host_blocks"]))
        assert (m.kv_host_bytes_g.labels("nano").value
                == float(st["host_bytes"]))
        assert m.kv_promote_backlog_g.labels("nano").value == 3.0
    finally:
        eng.stop()


def test_demoted_entries_are_affinity_eligible():
    """prefix_affinity_tokens consults the spill tier, so replica
    dispatch (serving/replicas.py) routes a session back to the replica
    holding its DEMOTED prefix — promotion beats a stranger's cold
    prefill."""
    eng = _engine()
    try:
        eng.generate(PROMPT)
        ids = eng.affinity_token_ids(TURN2)
        warm = eng.prefix_affinity_tokens(ids)
        assert warm > 0
        _demote_parked(eng)
        assert eng.prefix_cache.stats()["entries"] == 0
        assert eng.prefix_affinity_tokens(ids) == warm
    finally:
        eng.stop()
