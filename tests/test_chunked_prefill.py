"""Disaggregated chunked prefill (ISSUE 9): long cold prompts are
absorbed one fixed chunk per scheduler tick instead of one monolithic
prefill call, byte-identically under greedy, and the in-flight prefill
is a first-class scheduler citizen — cancel-and-requeue under KV
pressure, KV-aware admission accounting, drain, and stop all treat it
like admitted work.

Fast deterministic tests only; the interference measurement is the
chip benchmark's long-prompt cell (benchmark/traffic/long-prompt.json).
"""

import dataclasses
import queue
import threading
import time

import pytest

from distributed_llm_tpu.config import tiny_cluster
from distributed_llm_tpu.engine.batching import (ContinuousBatchingEngine,
                                                 _Request)
from distributed_llm_tpu.engine.manager import EngineManager

# Past the 32 bucket on the tiny ladder (bucket 64): chunked at every
# chunk size the 16-block geometry allows.
LONG_Q = ("user: tell me about rivers lakes mountains oceans deltas "
          "streams glaciers valleys canyons plateaus islands forests")
SHORT_Q = "user: short question about rivers"


def _tier(**kw):
    defaults = dict(max_new_tokens=8, decode_batch=2,
                    enable_prefix_cache=False)
    defaults.update(kw)
    return dataclasses.replace(tiny_cluster().nano, **defaults)


def _engine(**kw):
    return ContinuousBatchingEngine(_tier(**kw), seed=11)


# -- config validation -------------------------------------------------------

def test_chunk_tokens_must_page_evenly():
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        _engine(prefill_chunk_tokens=24)     # not a multiple of bs=16
    # 0/None disable chunking instead of erroring.
    for off in (0, None):
        eng = _engine(prefill_chunk_tokens=off)
        assert eng.chunk_tokens == 0 and not eng._chunk_gate(64)


def test_budget_floors_at_one_chunk():
    eng = _engine(prefill_chunk_tokens=32, prefill_chunk_budget=16)
    assert eng.chunk_budget == 32            # always ≥ one whole chunk


# -- byte identity -----------------------------------------------------------

def test_byte_identical_greedy_at_every_chunk_size():
    """The tentpole contract: the chunked path changes WHEN prompt K/V
    is written, never what is sampled — greedy output matches the
    monolithic prefill exactly at every chunk size."""
    mono = _engine(prefill_chunk_tokens=None)
    try:
        ref = mono.generate(LONG_Q)
    finally:
        mono.stop()
    assert ref.gen_tokens > 0
    for c in (16, 32, 48):
        eng = _engine(prefill_chunk_tokens=c)
        try:
            got = eng.generate(LONG_Q)
            assert got.token_ids == ref.token_ids, f"chunk={c}"
            assert got.prompt_tokens == ref.prompt_tokens
            # The long prompt really went through the chunk machinery:
            # its (chunk, window) program family exists and the TOP
            # bucket's monolithic prefill program was never minted.
            keys = eng._compiled.get("chunk_prefill", set())
            assert keys and all(k[0] == c for k in keys), keys
            assert all(k[1] in eng._chunk_windows for k in keys), keys
            assert 64 not in eng._compiled.get("prefill", set())
        finally:
            eng.stop()


def test_short_prompts_keep_the_monolithic_path():
    """A prompt fitting one chunk already meets the TBT bound: it keeps
    the warm prefill-bucket path and mints no chunk programs."""
    eng = _engine(prefill_chunk_tokens=32)
    try:
        res = eng.generate(SHORT_Q)          # bucket 16 or 32, ≤ chunk
        assert res.gen_tokens > 0
        assert not eng._compiled.get("chunk_prefill")
    finally:
        eng.stop()


# -- interleaving ------------------------------------------------------------

def test_decode_streams_while_long_prompt_absorbs():
    """An active stream keeps producing tokens while a long prompt is
    mid-absorption (the in-flight prefill is observable via
    prefill_stats), and both requests finish correctly."""
    eng = _engine(prefill_chunk_tokens=16, max_new_tokens=24)
    try:
        solo = eng.generate(LONG_Q)          # warm + the reference text
        handle = eng.generate_stream(SHORT_Q)
        it = iter(handle)
        next(it)                             # primed: decoding is live
        req = eng.submit(LONG_Q)
        saw_inflight = False
        for _ in it:                         # stream continues to flow
            saw_inflight = (saw_inflight
                            or eng.prefill_stats()["inflight"] == 1)
        assert req.done.wait(timeout=120)
        assert req.error is None
        assert req.result.token_ids == solo.token_ids
        assert saw_inflight, ("the short stream never overlapped the "
                              "long prompt's absorption")
    finally:
        eng.stop()


# -- scheduler citizenship ---------------------------------------------------

def test_kv_stats_account_inflight_prefill_demand():
    """KV-aware admission must see the half-prefilled prompt's remaining
    block demand: kv_stats carries pending blocks + token backlog, and
    queue_depth/pending_work count the in-flight prefill."""
    eng = _engine(prefill_chunk_tokens=16)
    req = _Request(history="x", max_new_tokens=None, temperature=None)
    ids = list(range(40))
    eng._start_prefill(req, 0, ids, len(ids), 64, 8)
    st = eng.kv_stats()
    assert st["prefill_pending_blocks"] == 3      # ceil(40/16), none held
    assert st["prefill_backlog_tokens"] == 40
    assert eng.queue_depth() == 1 and eng.pending_work() == 1
    assert eng.slot_stats()["prefill_inflight"] == 1
    assert eng.prefill_stats()["backlog_tokens"] == 40
    # Cancel-and-requeue: blocks free, the request re-enters at the
    # scheduler head, and the accounting returns to zero.
    eng._cancel_prefill("test")
    assert eng.prefill_cancelled_total == 1
    assert eng._prefill is None and eng._head[0] is req
    st = eng.kv_stats()
    assert st["prefill_pending_blocks"] == 0
    assert st["prefill_backlog_tokens"] == 0


def test_admission_gate_subtracts_prefill_pending_blocks():
    """serving/tiers.py: the projected-demand gate treats the in-flight
    prefill's remaining blocks as spoken for."""
    from distributed_llm_tpu.serving.tiers import TierClient

    class _Eng:
        concurrent_safe = True

        def kv_stats(self):
            return {"free_blocks": 6, "reclaimable_blocks": 0,
                    "prefill_pending_blocks": 4}

        def max_demand_blocks(self):
            return 5

        def projected_demand_blocks(self, history, max_new_tokens=None):
            return 3                          # > 6 - 4 = 2 → reject

    class _Mgr:
        def __init__(self):
            self._engine = _Eng()

    tier = _tier(kv_admission=True)
    client = TierClient(tier, _Mgr())
    demand, supply = client._kv_admission_args("hello")
    assert (demand, supply) == (3, 2)
    err = client.admission.try_admit(demand, supply)
    assert err is not None and "KV demand" in err


def test_dry_pool_stall_reports_no_progress():
    """A prefill that cannot allocate its next chunk's blocks reports
    progressed=False (the scheduler's solo-prefill branch backs off on
    it instead of hot-spinning on an allocator nothing will refill) and
    stays in flight for a later retry."""
    eng = _engine(prefill_chunk_tokens=16)
    req = _Request(history="long", max_new_tokens=None, temperature=None)
    eng._start_prefill(req, 0, list(range(40)), 40, 64, 8)
    hog = eng.allocator.alloc(eng.allocator.available)  # drain the pool
    assert eng._advance_prefill() is False
    assert eng._prefill is not None and eng._prefill.consumed == 0
    eng.allocator.free(hog)


def test_growth_starvation_cancels_prefill_before_preempting_decoders():
    """Deterministic victim-priority check: with the pool drained and a
    decoding slot needing growth, _ensure_growth cancels the in-flight
    prefill (freeing its blocks) instead of preempting the decoder."""
    from distributed_llm_tpu.engine.batching import _Slot

    eng = _engine(prefill_chunk_tokens=16, max_new_tokens=24)
    req_dec = _Request(history="decoder", max_new_tokens=None, temperature=None)
    req_dec.admit_seq = 0
    blocks = eng.allocator.alloc(1)
    slot = _Slot(request=req_dec, blocks=blocks, prompt_len=14, budget=24,
                 temperature=0.0, ttft_ms=1.0, tokens=[5],
                 prompt_ids=(1, 2), max_blocks=3)
    eng._slots[0] = slot
    eng._pos[0] = 15                          # next tick crosses a block
    req_pf = _Request(history="long", max_new_tokens=None, temperature=None)
    eng._start_prefill(req_pf, 1, list(range(40)), 40, 64, 8)
    # The prefill holds EVERYTHING else: the pool is dry for growth.
    eng._prefill.blocks.extend(eng.allocator.alloc(eng.allocator.available))
    eng._ensure_growth([0])
    assert eng.prefill_cancelled_total == 1
    assert eng._prefill is None and eng._head[0] is req_pf
    assert eng.preempted_total == 0           # the decoder was NOT touched
    assert len(slot.blocks) >= 2              # growth succeeded
    assert eng._slots[0] is slot


def test_tight_pool_under_contention_stays_byte_identical():
    """End-to-end pressure: a decoding elder and a chunked long prompt
    fight over a minimal pool — whatever mix of prefill cancels and
    decode preemptions the interleaving produces, both outputs match
    their solo runs and every block returns to the pool."""
    def build():
        return _engine(prefill_chunk_tokens=16, prefill_chunk_budget=16,
                       max_new_tokens=24, kv_pool_blocks=5)

    solo_eng = build()
    try:
        solo_short = solo_eng.generate(SHORT_Q)
        solo_long = solo_eng.generate(LONG_Q)
    finally:
        solo_eng.stop()

    eng = build()
    res = {}
    try:
        t = threading.Thread(
            target=lambda: res.__setitem__("short",
                                           eng.generate(SHORT_Q)))
        t.start()
        time.sleep(0.02)                      # elder decoding first
        res["long"] = eng.generate(LONG_Q)
        t.join(timeout=120)
        assert res["short"].token_ids == solo_short.token_ids
        assert res["long"].token_ids == solo_long.token_ids
        assert eng.allocator.available == eng.paged.num_blocks - 1
    finally:
        eng.stop()
    assert eng.allocator.available == eng.paged.num_blocks - 1


def test_preempted_chunked_request_replays_byte_identically():
    """PR 5 interaction: a request that was PREEMPTED mid-decode replays
    its prompt+prefix through the CHUNKED path when the replay bucket
    exceeds one chunk — the continuation must still be byte-identical."""
    eng = _engine(prefill_chunk_tokens=16, max_new_tokens=24,
                  kv_pool_blocks=5)
    solo = {}
    probe_b = "what is the tallest mountain on the continent of asia now"
    ref = ContinuousBatchingEngine(
        _tier(prefill_chunk_tokens=16, max_new_tokens=24), seed=11)
    try:
        solo["a"] = ref.generate(LONG_Q).text
        solo["b"] = ref.generate(probe_b).text
    finally:
        ref.stop()
    res = {}
    try:
        t = threading.Thread(
            target=lambda: res.__setitem__("a", eng.generate(LONG_Q)))
        t.start()
        time.sleep(0.05)
        res["b"] = eng.generate(probe_b)
        t.join(timeout=120)
        assert res["a"].text == solo["a"]
        assert res["b"].text == solo["b"]
    finally:
        eng.stop()


def test_drain_waits_out_half_prefilled_request():
    """Graceful drain counts the in-flight prefill as pending work and
    waits for it to finish decoding, not just for the active slots."""
    tier = _tier(prefill_chunk_tokens=16, prefill_chunk_budget=16,
                 max_new_tokens=24, drain_timeout_s=30.0)
    manager = EngineManager(tier, warmup_on_start=False)
    manager.start_server()
    try:
        eng = manager.engine()
        eng.generate("warm", max_new_tokens=2)
        req = eng.submit(LONG_Q)
        deadline = time.time() + 30
        while (eng.prefill_stats()["inflight"] == 0 and not req.done.is_set()
               and time.time() < deadline):
            time.sleep(0.001)
        summary = manager.drain()
        assert req.done.is_set()
        assert req.error is None and req.result.gen_tokens > 0
        assert summary["aborted"] == 0
        assert summary["in_flight_at_start"] >= 1
    finally:
        manager.stop_server()


def test_stop_fails_half_prefilled_request_with_shape():
    from distributed_llm_tpu.engine.batching import EngineStoppedError

    eng = _engine(prefill_chunk_tokens=16, prefill_chunk_budget=16,
                  max_new_tokens=24)
    eng.generate("warm", max_new_tokens=2)
    req = eng.submit(LONG_Q)
    deadline = time.time() + 30
    while (eng.prefill_stats()["inflight"] == 0 and not req.done.is_set()
           and time.time() < deadline):
        time.sleep(0.0005)
    eng.stop()
    assert req.done.wait(timeout=10)
    if req.error is not None:                 # raced completion is legal
        assert isinstance(req.error, EngineStoppedError)
        assert "error" in req.error.shape
    assert eng.allocator.available == eng.paged.num_blocks - 1


def test_stop_mid_prefill_leaves_zero_live_blocks():
    """Regression (ISSUE 19 fix): stop() cancels the in-flight chunked
    prefill — freeing its blocks and unpinning its prefix entry — and,
    with DLLM_KV_LEAK_CHECK armed (conftest arms it suite-wide),
    asserts zero live pool blocks before returning.  A reintroduced
    leak therefore fails INSIDE stop(), not as collateral damage in
    whatever test runs next."""
    eng = _engine(prefill_chunk_tokens=16, prefill_chunk_budget=16,
                  max_new_tokens=24, enable_prefix_cache=True,
                  prefix_cache_entries=4)
    req = None
    try:
        eng.generate("warm", max_new_tokens=2)
        req = eng.submit(LONG_Q)
        deadline = time.time() + 30
        while (eng.prefill_stats()["inflight"] == 0
               and not req.done.is_set() and time.time() < deadline):
            time.sleep(0.0005)
    finally:
        eng.stop()          # leak-check assert lives in here
    assert eng.allocator.ref_stats()["allocated_blocks"] == 0
    assert req.done.wait(timeout=10)


# -- observability -----------------------------------------------------------

def test_prefill_chunk_metrics_and_trace_split():
    """The chunk histogram observes every grant, the queue-wait stamp is
    split into admission-wait vs prefill-wait, and the chunk spans land
    in the request's tree."""
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.obs.spans import RequestTrace, use_trace

    hist = get_observability().m.prefill_chunk_ms.labels("nano")
    before = hist.count
    eng = _engine(prefill_chunk_tokens=16)
    try:
        trace = RequestTrace("req-1")
        with use_trace(trace):
            req = eng.submit(LONG_Q)
        assert req.done.wait(timeout=120) and req.error is None
        assert hist.count >= before + 2       # ≥2 chunks for the 64 bucket
        assert trace.attrs.get("admission_wait_ms") is not None
        assert trace.attrs.get("prefill_wait_ms") is not None
        assert (trace.attrs["queue_wait_ms"]
                == trace.attrs["admission_wait_ms"])
        names = [c.name for c in (trace.root.children or ())]
        assert names.count("prefill_chunk") >= 2, names
    finally:
        eng.stop()


def test_sampler_gauge_field_covers_prefill_backlog():
    """obs/sampler.py mirrors prefill_backlog_tokens to the
    dllm_prefill_backlog gauge when the collect payload carries it."""
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.obs.sampler import SystemStateSampler

    m = get_observability().m
    sampler = SystemStateSampler(
        lambda: {"nano": {"prefill_backlog_tokens": 37}}, metrics=m)
    sampler.sample_once()
    assert m.prefill_backlog_g.labels("nano").value == 37.0


# -- a chunk rides behind the tick (ISSUE 32) ---------------------------------
#
# Tier 1 serves the fused ragged tick by default and the chip's cells the
# dense windowed one: every case below runs on both.

TICKS = pytest.mark.parametrize("ragged", [True, False],
                                ids=["ragged", "dense"])


def _overlap_engine(ragged, **kw):
    defaults = dict(prefill_chunk_tokens=16, max_new_tokens=40,
                    decode_batch=4, attention_ragged=ragged)
    defaults.update(kw)
    return _engine(**defaults)


def _neighbour_prompt(i):
    return f"user: hi {i}"                    # under one chunk: monolithic


def _prime_neighbours(eng, n):
    """``n`` requests that are decoding when this returns (each has
    produced its first token)."""
    reqs = []
    for i in range(n):
        req = eng.submit(_neighbour_prompt(i), token_queue=queue.Queue())
        req.token_queue.get(timeout=120)
        reqs.append(req)
    return reqs


def _finished(reqs):
    """Wait the requests out; whether all ended without an error."""
    return all(r.done.wait(timeout=120) and r.error is None for r in reqs)


def _watch_chunks(eng):
    """Wrap every chunk program the engine hands out: records, at each
    dispatch, whether the prefill already had an unresolved chunk."""
    seen = []
    real = eng._chunk_prefill_fn

    def watched(bucket, window):
        fn = real(bucket, window)

        def call(*args):
            pf = eng._prefill
            seen.append(pf is not None and pf.pending is not None)
            return fn(*args)
        return call
    eng._chunk_prefill_fn = watched
    return seen


@TICKS
def test_chunk_rides_between_the_ticks_dispatch_and_its_fetch(ragged):
    """(a) With a prefill in flight beside active slots, a tick's record
    reads dispatch -> chunk_prefill -> fetch, the chunk's section nests
    in ``decode`` without entering what the tick cost, and no chunk is
    dispatched while another is unresolved."""
    eng = _overlap_engine(ragged)
    try:
        eng.generate(LONG_Q)                  # compile outside the watch
        unresolved_at_dispatch = _watch_chunks(eng)
        mark = eng.profiler._seq
        neighbours = _prime_neighbours(eng, 2)
        assert _finished([eng.submit(LONG_Q)] + neighbours)
        assert unresolved_at_dispatch and not any(unresolved_at_dispatch)
        ticks = [r for r in eng.profiler.records()
                 if r["seq"] > mark and r["slots"]]
        riding = 0
        for rec in ticks:
            spans = sorted(rec["spans"], key=lambda s: s[1])
            names = [n for n, *_ in spans
                     if n in ("dispatch", "chunk_prefill", "fetch")]
            if "chunk_prefill" not in names:
                continue
            if names[:3] != ["dispatch", "chunk_prefill", "fetch"]:
                # A landing or a budget's rest after the emit only.
                assert names[:2] == ["dispatch", "fetch"], rec
                continue
            riding += 1
            _, lo, dur, *_ = next(s for s in spans if s[0] == "decode")
            _, at, took, *_ = next(s for s in spans
                                   if s[0] == "chunk_prefill")
            assert lo <= at and at + took <= lo + dur + 1e-6
        assert riding >= 2, [r["spans"] for r in ticks]
        # What the ticks cost is their launch and their fetch: the
        # chunk's section inside ``decode`` is no part of it.
        prof = eng.profiler
        totals = prof.self_totals()
        assert prof.total_ms("decode") == pytest.approx(
            totals["decode"] + totals["dispatch"] + totals["fetch"])
        assert prof.total_ms("decode") == pytest.approx(
            sum(eng.tick_ms), rel=0.05)
    finally:
        eng.stop()


@TICKS
@pytest.mark.parametrize("neighbours,budget", [(0, None), (1, None),
                                               (3, None), (2, 32)])
def test_overlapped_admission_is_byte_identical(ragged, neighbours, budget):
    """(b) The chunked admission's stream equals the monolithic one with
    0, 1 and several decoding neighbours, and with a budget of two
    chunks a pass; the neighbours' streams equal their solo runs."""
    mono = _overlap_engine(ragged, prefill_chunk_tokens=None)
    try:
        ref = mono.generate(LONG_Q).token_ids
        ref_n = [mono.generate(_neighbour_prompt(i)).token_ids
                 for i in range(neighbours)]
    finally:
        mono.stop()
    eng = _overlap_engine(ragged, prefill_chunk_budget=budget)
    try:
        reqs = _prime_neighbours(eng, neighbours)
        long_req = eng.submit(LONG_Q)
        assert _finished([long_req] + reqs)
        assert long_req.result.token_ids == ref
        assert [r.result.token_ids for r in reqs] == ref_n
        st = eng.prefill_stats()
        assert st["chunks_total"] >= 2
        if not neighbours:
            assert st["chunks_overlapped_total"] == 0
    finally:
        eng.stop()                            # leak check inside


def _half_prefilled(eng):
    """An engine (scheduler not running) whose in-flight prefill has one
    chunk dispatched and NOT resolved; returns the request."""
    req = _Request(history=LONG_Q, max_new_tokens=None, temperature=None)
    assert eng._admit(req, 0) and eng._prefill is not None
    assert eng._dispatch_chunk(eng._prefill, overlapped=False)
    assert eng._prefill.pending is not None and eng._prefill.blocks
    return req


@TICKS
@pytest.mark.parametrize("how", ["cancel", "capture", "stop"])
def test_early_end_settles_the_unresolved_chunk(ragged, how):
    """(c) Whatever ends a prefill early first settles its unresolved
    chunk: the allocator comes back clean (DLLM_KV_LEAK_CHECK is armed
    suite-wide, inside stop()), and a re-queued request re-admits to the
    bytes of an undisturbed run."""
    from distributed_llm_tpu.engine.batching import EngineStoppedError

    ref_eng = _overlap_engine(ragged)
    try:
        ref = ref_eng.generate(LONG_Q).token_ids
    finally:
        ref_eng.stop()
    eng = _overlap_engine(ragged)
    try:
        req = _half_prefilled(eng)
        pf = eng._prefill
        if how == "cancel":
            eng._cancel_prefill("kv pressure (test)")
            assert eng._prefill is None and pf.pending is None
            assert eng.allocator.ref_stats()["allocated_blocks"] == 0
            assert eng._head[0] is req
            eng.start()                       # re-admits from chunk 0
            assert req.done.wait(timeout=120) and req.error is None
            assert req.result.token_ids == ref
        elif how == "capture":
            got = eng.capture_requests()
            assert got == [req] and pf.pending is None
            assert eng.allocator.ref_stats()["allocated_blocks"] == 0
            assert eng.adopt_requests(got) == 1
            assert req.done.wait(timeout=120) and req.error is None
            assert req.result.token_ids == ref
        else:
            eng.stop()
            assert pf.pending is None and req.done.is_set()
            assert isinstance(req.error, EngineStoppedError)
    finally:
        eng.stop()
    assert eng.allocator.ref_stats()["allocated_blocks"] == 0


class _Poisoned:
    """A chunk output whose device work "failed": the error surfaces at
    the wait, as a runtime failure of an asynchronous program does."""

    def block_until_ready(self):
        raise RuntimeError("chunk program failed on the device")


@TICKS
@pytest.mark.parametrize("where", ["dispatch", "settle"])
def test_failed_chunk_fails_its_own_request_only(ragged, where):
    """(d) A chunk program that raises — at its launch, or one chunk
    late at the wait for it — fails the prefill's own request; the
    decoding neighbour finishes with its solo bytes and the engine
    keeps serving."""
    eng = _overlap_engine(ragged)
    try:
        solo_long = eng.generate(LONG_Q).token_ids
        solo_n = eng.generate(_neighbour_prompt(0)).token_ids
        real = eng._chunk_prefill_fn
        calls = []

        def failing(bucket, window):
            fn = real(bucket, window)

            def call(params, pool, *args):
                calls.append(where)
                if len(calls) == 2:
                    if where == "dispatch":
                        raise RuntimeError("chunk program failed to launch")
                    _, pool = fn(params, pool, *args)
                    return _Poisoned(), pool
                return fn(params, pool, *args)
            return call
        eng._chunk_prefill_fn = failing
        neighbours = _prime_neighbours(eng, 1)
        req = eng.submit(LONG_Q)
        assert req.done.wait(timeout=120)
        assert isinstance(req.error, RuntimeError)
        assert "chunk program failed" in str(req.error)
        assert _finished(neighbours)
        assert neighbours[0].result.token_ids == solo_n
        eng._chunk_prefill_fn = real
        assert eng._prefill is None
        assert eng.generate(LONG_Q).token_ids == solo_long
    finally:
        eng.stop()                            # leak check inside


@TICKS
def test_failed_tick_does_not_take_the_riding_prefill_with_it(ragged,
                                                              monkeypatch):
    """The other direction of (d): the tick whose fetch fails takes its
    decoding slots, not the prefill whose LAST chunk rode behind it —
    the landing its handler skipped happens in the next pass, no chunk
    is dispatched past the prompt's end, and the bytes are the solo
    run's."""
    from distributed_llm_tpu.engine import batching

    eng = _overlap_engine(ragged)
    try:
        solo_long = eng.generate(LONG_Q).token_ids
        chunks = eng.prefill_stats()["chunks_total"]
        real = batching._fetch_tick
        fired = []

        def fetch(x):
            pf = eng._prefill
            if (not fired and pf is not None and pf.pending is not None
                    and pf.consumed >= pf.total):
                # A request that the next pass admits: that pass has an
                # active slot again, so it reaches the ride.
                fired.append(eng.submit(_neighbour_prompt(1)))
                raise RuntimeError("tick failed on the device")
            return real(x)
        monkeypatch.setattr(batching, "_fetch_tick", fetch)
        r, = _prime_neighbours(eng, 1)
        req = eng.submit(LONG_Q)
        assert req.done.wait(timeout=120) and r.done.wait(timeout=120)
        assert fired and isinstance(r.error, RuntimeError)
        assert fired[0].done.wait(timeout=120) and fired[0].error is None
        assert req.error is None and req.result.token_ids == solo_long
        assert eng.prefill_stats()["chunks_total"] == 2 * chunks
    finally:
        eng.stop()                            # leak check inside


def test_latent_prefill_expert_counts_survive_the_overlap():
    """(e) The latent family's chunk brings its experts' assignment
    counts with its token: a fixed prompt's ``expert_tokens.prefill``
    are what the chunk programs return one at a time, each waited for
    (the order before ISSUE 32), solo and beside a decoding neighbour."""
    import numpy as np
    from distributed_llm_tpu.config import TierConfig

    def build():
        return ContinuousBatchingEngine(TierConfig(
            name="nano", model_preset="latent_test", decode_batch=4,
            kv_block_size=16, prefill_buckets=(16, 32, 64, 128),
            prefill_chunk_tokens=16, max_new_tokens=24,
            enable_prefix_cache=False), seed=3)

    def prefill_counts(eng):
        return np.asarray(eng.moe_stats()["expert_tokens"]["prefill"])

    # One at a time, each waited for before the next is dispatched.
    serial = build()
    try:
        req = _Request(history=LONG_Q, max_new_tokens=None,
                       temperature=None)
        assert serial._admit(req, 0)
        pf = serial._prefill
        chunks = 0
        while pf.consumed < pf.total:
            assert serial._dispatch_chunk(pf, overlapped=False)
            serial._settle_chunk(pf)
            chunks += 1
        want = prefill_counts(serial)
        assert serial.moe_stats()["steps"]["prefill"] == chunks >= 2
        serial._cancel_prefill("test")
    finally:
        serial.stop()
    cfg = serial.cfg
    assert want.sum() == chunks * 16 * cfg.experts_per_token \
        * want.shape[0]

    for neighbours in (0, 1):
        eng = build()
        try:
            reqs = _prime_neighbours(eng, neighbours)
            assert eng.generate(LONG_Q).gen_tokens > 0
            assert _finished(reqs)
            np.testing.assert_array_equal(prefill_counts(eng), want)
            assert eng.moe_stats()["steps"]["prefill"] == chunks
            if neighbours:
                assert eng.prefill_stats()["chunks_overlapped_total"] >= 1
        finally:
            eng.stop()


# -- the counter that says how often the overlap engages ----------------------

@TICKS
def test_overlap_counter(ragged):
    """0 of 0 for prompts under one chunk; a solo prefill's chunks count
    as dispatched but not overlapped; with active slots every chunk of
    the prompt rides behind a tick.  /stats and /metrics carry both."""
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.utils.telemetry import engine_stats

    m = get_observability().m
    rode = m.prefill_chunks.labels("nano", "behind_tick")
    alone = m.prefill_chunks.labels("nano", "alone")
    rode0, alone0 = rode.value, alone.value
    # The neighbours outlive the prompt's absorption: 4 ticks of 4
    # steps hold its chunks, the cap is 64 tokens.
    eng = _overlap_engine(ragged, max_new_tokens=64)
    try:
        eng.generate(_neighbour_prompt(9))
        st = eng.prefill_stats()
        assert (st["chunks_total"], st["chunks_overlapped_total"],
                st["overlap_share"]) == (0, 0, None)
        eng.generate(LONG_Q, max_new_tokens=4)    # solo
        st = eng.prefill_stats()
        solo_chunks = st["chunks_total"]
        assert solo_chunks >= 2 and st["chunks_overlapped_total"] == 0
        assert st["overlap_share"] == 0.0
        neighbours = _prime_neighbours(eng, 2)
        assert _finished([eng.submit(LONG_Q, max_new_tokens=4)])
        st = eng.prefill_stats()
        assert _finished(neighbours)
        assert st["chunks_total"] == 2 * solo_chunks
        assert st["chunks_overlapped_total"] == solo_chunks
        assert st["overlap_share"] == 0.5
        assert engine_stats(eng)["prefill"]["chunks_total"] \
            == 2 * solo_chunks
        assert rode.value - rode0 == solo_chunks
        assert alone.value - alone0 == solo_chunks
    finally:
        eng.stop()


# -- the lane's window ladder doubles (ISSUE 42) -------------------------------
#
# The tiny presets stop at 256 positions, where both ladders are {256}:
# these cases serve the same tiny models over longer tables.

def _spanned_tier(monkeypatch, preset, span, **kw):
    """A tier of ``preset``'s model over a table of ``span`` positions
    (blocks of 16), one slot's worth of blocks and a few to spare."""
    from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig

    model = {k: kw.pop(k) for k in ("attn_window", "tokenizer", "vocab_size")
             if k in kw}
    cfg = dataclasses.replace(MODEL_PRESETS[preset],
                              name=f"{preset}_{span}", max_seq_len=span,
                              **model)
    monkeypatch.setitem(MODEL_PRESETS, cfg.name, cfg)
    defaults = dict(
        name="nano", model_preset=cfg.name, decode_batch=2,
        kv_block_size=16, prefill_buckets=(32, 64, 128, 512, 2048),
        prefill_chunk_tokens=256, max_new_tokens=4,
        enable_prefix_cache=False, kv_pool_blocks=span // 16 + 8)
    defaults.update(kw)
    return TierConfig(**defaults)


def _prefill_by_hand(eng, ids, trace=None):
    """Chunk ``ids`` into slot 0 one settled chunk at a time (no
    scheduler thread in the way).  Gives the in-flight prefill and the
    token its last chunk sampled; the caller cancels it."""
    req = _Request(history="x", max_new_tokens=None, temperature=None,
                   trace=trace)
    eng._start_prefill(req, 0, list(ids), len(ids), 2048, 4)
    pf = eng._prefill
    first = None
    while pf.consumed < pf.total:
        assert eng._dispatch_chunk(pf, overlapped=False)
        first = eng._settle_chunk(pf)
    return pf, int(first)


def _fake_chunk_programs(eng):
    """Stand-ins for the chunk programs: each is noted as compiled under
    its (bucket, window) key, hands the pool back and computes nothing.
    Gives the list the calls' keys are appended to."""
    import jax.numpy as jnp
    called = []

    def fake(bucket, window):
        eng._note_compile("chunk_prefill", (bucket, window))

        def call(params, pool, *args):
            called.append((bucket, window))
            return jnp.int32(7), pool
        return call
    eng._chunk_prefill_fn = fake
    return called


@pytest.mark.parametrize("span,lane,reuse", [
    (512, [256, 512], [256, 512]),
    (1024, [256, 1024], [256, 1024]),
    (2048, [256, 1024, 2048], [256, 1024, 2048]),
    (4096, [256, 1024, 2048, 4096], [256, 1024, 4096]),
    (5120, [256, 1024, 2048, 4096, 5120], [256, 1024, 5120]),
    (8192, [256, 1024, 2048, 4096, 8192], [256, 1024, 8192]),
])
def test_window_ladders_by_span(span, lane, reuse):
    """The lane's rungs double from 1024 to the span; the prefix-reuse
    path keeps {256, 1024, span}; both block-aligned whatever the block."""
    from distributed_llm_tpu.engine.batching import _window_ladder

    for bs in (16, 64):
        assert _window_ladder(span, bs, doubling=True) == lane
        assert _window_ladder(span, bs, doubling=False) == reuse
    # A block that does not divide a rung rounds it up to whole blocks.
    assert _window_ladder(3 * 1280, 80, doubling=True) == [
        320, 1040, 2080, 3840]


def test_engine_derives_both_ladders_from_its_span(monkeypatch):
    eng = ContinuousBatchingEngine(
        _spanned_tier(monkeypatch, "nano_test", 8192), seed=11)
    try:
        assert eng._chunk_windows == [256, 1024, 2048, 4096, 8192]
        assert eng._reuse_windows == [256, 1024, 8192]
    finally:
        eng.stop()


def test_a_1792_token_prompt_climbs_the_ladder(monkeypatch):
    """Seven chunks of 256 over an 8192 table: no chunk attends the span,
    and the engine's counters say 1.32 positions attended a position
    written (3.89 on the three-rung ladder) in prefill_stats, /stats'
    engine block and the registry /metrics renders."""
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.obs.spans import RequestTrace
    from distributed_llm_tpu.utils.telemetry import engine_stats

    m = get_observability().m
    attended = m.prefill_window_positions.labels("nano")
    written = m.prefill_written_positions.labels("nano")
    attended0, written0 = attended.value, written.value
    eng = ContinuousBatchingEngine(
        _spanned_tier(monkeypatch, "nano_test", 8192), seed=11)
    try:
        st = eng.prefill_stats()
        assert (st["window_positions_total"], st["written_positions_total"],
                st["window_over_written"]) == (0, 0, None)
        trace = RequestTrace("req-1792")
        ids = [5 + i % 200 for i in range(1792)]
        _prefill_by_hand(eng, ids, trace=trace)
        eng._cancel_prefill("test")
        spans = [c for c in trace.root.children
                 if c.name == "prefill_chunk"]
        assert [s.attrs["window"] for s in spans] == [
            256, 1024, 1024, 1024, 2048, 2048, 2048]
        assert [s.attrs["start"] for s in spans] == list(range(0, 1792, 256))
        assert eng._compiled["chunk_prefill"] == {
            (256, 256), (256, 1024), (256, 2048)}
        st = eng.prefill_stats()
        assert st["window_positions_total"] == 256 + 3 * 1024 + 3 * 2048
        assert st["written_positions_total"] == 7168
        assert st["window_over_written"] == round(9472 / 7168, 4) == 1.3214
        assert engine_stats(eng)["prefill"]["window_over_written"] == 1.3214
        assert attended.value - attended0 == 9472
        assert written.value - written0 == 7168
        text = get_observability().metrics.render()
        assert 'dllm_prefill_window_positions_total{tier="nano"}' in text
        assert 'dllm_prefill_written_positions_total{tier="nano"}' in text
        # A prompt that ends inside its last chunk is written to its own
        # length, not to the chunk's end.
        _prefill_by_hand(eng, ids[:300])
        eng._cancel_prefill("test")
        st = eng.prefill_stats()
        assert st["written_positions_total"] == 7168 + 256 + 300
        assert st["window_positions_total"] == 9472 + 256 + 1024
    finally:
        eng.stop()


def test_slid_back_sliver_takes_the_span(monkeypatch):
    """A chunk that would overrun the table is slid back against its end:
    it ends at the span, and the span's rung is the one that holds it."""
    eng = ContinuousBatchingEngine(_spanned_tier(
        monkeypatch, "nano_test", 4096, prefill_chunk_tokens=768), seed=11)
    try:
        called = _fake_chunk_programs(eng)
        req = _Request(history="x", max_new_tokens=None, temperature=None)
        eng._start_prefill(req, 0, [5] * 4000, 4000, 2048, 4)
        pf = eng._prefill
        pf.consumed = 5 * 768                  # 3840 + 768 > 4096
        assert eng._dispatch_chunk(pf, overlapped=False)
        assert called == [(768, 4096)]
        assert pf.consumed == pf.total == 4000
        st = eng.prefill_stats()
        assert (st["window_positions_total"],
                st["written_positions_total"]) == (4096, 4000)
        eng._cancel_prefill("test")
    finally:
        eng.stop()


@pytest.mark.parametrize("preset,model", [
    ("nano_test", {}), ("latent_test", {}), ("hybrid_test", {}),
    ("shared_kv_test", {"attn_window": 256}),
], ids=["dense", "latent", "hybrid", "shared_kv"])
def test_doubling_ladder_changes_nothing_but_the_window(monkeypatch, preset,
                                                        model):
    """A prompt past 1024 on the lane's ladder (its last chunk at the
    2048 rung) against an engine forced to the old three rungs (the same
    chunk at the span, 4096): the same first token from the same logits,
    and the same K/V, rings and rows in the pool — a narrower window
    drops only positions the mask already excluded."""
    import jax
    import numpy as np
    from distributed_llm_tpu.engine import batching

    logits = []
    real = batching._sample_batched

    def recording(lg, rng, temps):
        jax.debug.callback(lambda x: logits.append(np.asarray(x)), lg)
        return real(lg, rng, temps)
    monkeypatch.setattr(batching, "_sample_batched", recording)

    ids = [int(x) for x in np.random.default_rng(5).integers(3, 500, 1100)]
    got = {}
    for ladder in ("lane", "old"):
        eng = ContinuousBatchingEngine(
            _spanned_tier(monkeypatch, preset, 4096, **model), seed=3)
        try:
            if ladder == "old":
                eng._chunk_windows = list(eng._reuse_windows)
            pf, first = _prefill_by_hand(eng, ids)
            jax.effects_barrier()
            got[ladder] = (first, logits[-1], list(pf.blocks),
                           {k: np.asarray(v, np.float32)
                            for k, v in eng.pool.items()},
                           set(eng._compiled["chunk_prefill"]))
            eng._cancel_prefill("test")
        finally:
            eng.stop()
    (first, lg, blocks, pool, keys), (first_o, lg_o, blocks_o, pool_o,
                                      keys_o) = got["lane"], got["old"]
    assert keys == {(256, 256), (256, 1024), (256, 2048)}
    assert keys_o == {(256, 256), (256, 1024), (256, 4096)}
    assert first == first_o and blocks == blocks_o
    np.testing.assert_allclose(lg, lg_o, rtol=2e-2, atol=2e-2)
    assert int(lg.argmax()) == int(lg_o.argmax()) == first
    for name in pool:
        # The slot's blocks of the paged arrays; rings and rows whole.
        cut = (lambda a: a[:, blocks]) if name in ("k", "v", "c") \
            else (lambda a: a)
        assert np.abs(cut(pool[name])).sum() > 0 or name == "owner"
        np.testing.assert_allclose(cut(pool[name]), cut(pool_o[name]),
                                   rtol=2e-2, atol=1e-3, err_msg=name)


@pytest.mark.parametrize("span,lane_programs", [(4096, 4), (8192, 5)])
def test_warmup_compiles_nine_reuse_programs_and_every_lane_rung(
        monkeypatch, span, lane_programs):
    """The warm set: every (reuse bucket, coarse window) pair, 9 as
    before the lane's ladder doubled, and one (chunk, window) program a
    rung of the lane's — so neither path traces mid-serve."""
    from distributed_llm_tpu.obs import get_observability

    eng = ContinuousBatchingEngine(_spanned_tier(
        monkeypatch, "nano_test", span, enable_prefix_cache=True), seed=11)
    try:
        _fake_chunk_programs(eng)
        eng.warmup()
        keys = eng._compiled["chunk_prefill"]
        reuse = {k for k in keys if k[0] != 256}
        assert reuse == {(sb, w) for sb in (32, 64, 128)
                         for w in (256, 1024, span)}
        assert keys - reuse == {(256, w) for w in eng._chunk_windows}
        assert len(keys) == 9 + lane_programs
        gauge = get_observability().m.compiled_programs.labels(
            "nano", "chunk_prefill")
        assert gauge.value == 9 + lane_programs
    finally:
        eng.stop()


def test_reuse_admission_past_1024_takes_the_span(monkeypatch):
    """The prefix-reuse suffix chunk chooses from its own three rungs: a
    parked prefix of 1100 tokens and a 32-token suffix attend the span,
    where the lane would have taken 2048."""
    eng = ContinuousBatchingEngine(_spanned_tier(
        monkeypatch, "nano_test", 4096, tokenizer="byte", vocab_size=512,
        enable_prefix_cache=True, max_new_tokens=2), seed=11)
    try:
        called = _fake_chunk_programs(eng)
        turn = "q" * 1099                       # BOS + bytes: 1100 ids
        eng.generate(turn)
        lane = list(called)
        assert lane == [(256, 256)] + [(256, 1024)] * 3 + [(256, 2048)]
        eng.generate(turn + "and a few more words")
        assert called[len(lane):] == [(32, 4096)]
    finally:
        eng.stop()


def test_stats_and_metrics_endpoints_carry_the_window_counters():
    from distributed_llm_tpu.config import tiny_batched_cluster
    from distributed_llm_tpu.serving.app import create_app
    from distributed_llm_tpu.serving.router import Router

    tiny = tiny_batched_cluster()
    cluster = dataclasses.replace(tiny, nano=dataclasses.replace(
        tiny.nano, prefill_chunk_tokens=16, enable_prefix_cache=False,
        max_new_tokens=4))
    router = Router(strategy="token", benchmark_mode=True, cluster=cluster,
                    config={"token_threshold": 1000000})
    try:
        client = create_app(router=router).test_client()
        resp = client.post("/chat", json={"message": LONG_Q,
                                          "strategy": "token",
                                          "session_id": "w"})
        assert resp.status_code == 200
        pf = client.get("/stats").get_json()["tiers"]["nano"]["prefill"]
        assert pf["chunks_total"] >= 2
        assert pf["window_positions_total"] == 256 * pf["chunks_total"]
        assert pf["window_over_written"] == round(
            pf["window_positions_total"] / pf["written_positions_total"], 4)
        text = client.get("/metrics").text
        assert "# TYPE dllm_prefill_window_positions_total counter" in text
        assert 'dllm_prefill_written_positions_total{tier="nano"}' in text
    finally:
        router.drain()
