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
