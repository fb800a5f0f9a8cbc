"""A full batch keeps one tick queued (PR 52).

With every slot taken, no end known within the tick in flight, no
chunked prefill, a plain next tick, the carry whole and the next tick's
blocks there, the scheduler dispatches tick N+1 BEFORE it fetches tick N
(``ContinuousBatchingEngine._may_go_ahead``, the one predicate; forced
off here by patching it).  Everything else runs the settle-first order.

Requests are queued behind a gate and admitted in one pass, so the two
orders see the same admissions and differ in nothing but the order of a
pass.
"""

import dataclasses
import queue
import threading
import time

import numpy as np
import pytest

from distributed_llm_tpu.config import TierConfig
from distributed_llm_tpu.engine import batching
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine

STEPS = 4
PROMPTS = ("tell me about rivers and lakes and streams and oceans please",
           "what is the tallest mountain on the continent of asia today",
           "name three colours of the evening sky over the sea",
           "how do birds find their way home in the autumn")


def _tier(**kw):
    defaults = dict(name="nano", model_preset="nano_test", max_new_tokens=48,
                    prefill_buckets=(16, 32, 64), decode_batch=2,
                    kv_block_size=16, decode_steps_per_tick=STEPS)
    defaults.update(kw)
    return TierConfig(**defaults)


# Flow 17/18 of the verify notes: what each family serves with.
_FAMILY = dict(prefill_buckets=(16, 32, 64, 128), prefill_chunk_tokens=16)
FAMILIES = {
    "dense": _tier(),
    "latent": _tier(model_preset="latent_test", **_FAMILY),
    "hybrid": _tier(model_preset="hybrid_test", enable_prefix_cache=False,
                    **_FAMILY),
}


def _gate(engine):
    """Holds every queued request back until set: what is submitted
    before is admitted in one pass, in order."""
    gate = threading.Event()
    real = engine._next_request
    engine._next_request = lambda: real() if gate.is_set() else None
    return gate


def _log_order(engine):
    """Launches (``L``, ``La`` ahead), fetches (``F``), emits (``E``) and
    admissions (``A<slot>``) in the order the scheduler made them."""
    log = []
    launch, fetch = engine._launch_tick, engine._fetch_and_account
    emit, admit = engine._emit_plain, engine._admit

    def launching(active, ahead=False):
        log.append("La" if ahead else "L")
        return launch(active, ahead)

    def fetching(tick):
        log.append("F")
        return fetch(tick)

    def emitting(tick, toks):
        log.append("E")
        return emit(tick, toks)

    def admitting(req, ix):
        done = admit(req, ix)
        if done:
            log.append(f"A{ix}")
        return done

    engine._launch_tick, engine._fetch_and_account = launching, fetching
    engine._emit_plain, engine._admit = emitting, admitting
    return log


def _serve(engine, asks, order="ahead"):
    """``asks`` (prompt, max_new_tokens, temperature) queued together,
    admitted together; their requests once all are done."""
    if order == "settle":
        engine._may_go_ahead = lambda tick: False
    gate = _gate(engine)
    reqs = [engine.submit(p, max_new_tokens=n, temperature=t)
            for p, n, t in asks]
    gate.set()
    for r in reqs:
        assert r.done.wait(timeout=180)
    return reqs


# -- (a) the streams ----------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sampled_streams_are_those_of_the_settle_first_order(family):
    """Two requests on two slots at a temperature of 0.8, the shorter
    one ending at its budget so that full and partial batches both run:
    token for token what the settle-first order gives (the key chain is
    on the device and the batch's shape does not change)."""
    asks = [(PROMPTS[0], 45, 0.8), (PROMPTS[1], 25, 0.8)]
    streams, ahead = {}, {}
    for order in ("ahead", "settle"):
        engine = ContinuousBatchingEngine(FAMILIES[family], seed=5)
        try:
            reqs = _serve(engine, asks, order)
            assert all(r.error is None for r in reqs)
            streams[order] = [r.result.token_ids for r in reqs]
            ahead[order] = engine.ticks_ahead_total
        finally:
            engine.stop()
    assert streams["ahead"] == streams["settle"]
    assert ahead["ahead"] >= 2 and ahead["settle"] == 0


# -- (b) an end the host could not foresee -------------------------------------

def _solo(prompt, n):
    engine = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        return engine.generate(prompt, max_new_tokens=n).token_ids
    finally:
        engine.stop()


def test_an_eos_under_a_tick_in_flight_ends_the_stream_and_wastes_one_tick():
    """Greedy, two slots, a third request waiting.  Tick 2's second step
    gives slot 0 an EOS while tick 3 is in flight: slot 0's stream ends
    at the EOS, tick 3 is settled BEFORE slot 0 takes the third request,
    that request's stream is what it is alone (nothing of the dead one),
    the neighbour's too, and the waste is one tick's steps."""
    engine = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        real = engine._decode_step()
        calls = []

        def tick(params, pool, tables, pos, cur, temps, key):
            calls.append(engine.ticks_ahead_total)
            out = real(params, pool, tables, pos, cur, temps, key)
            if len(calls) == 3:
                (toks, *rest), pool2 = out
                toks = toks.at[1, 0].set(engine.tokenizer.eos_id)
                out = (toks, *rest), pool2
            return out

        engine._decode_fn = tick
        log = _log_order(engine)
        gate = _gate(engine)
        streamed = queue.Queue()
        cut = engine.submit(PROMPTS[0], max_new_tokens=24,
                            token_queue=streamed)
        beside = engine.submit(PROMPTS[1], max_new_tokens=24)
        third = engine.submit(PROMPTS[2], max_new_tokens=9)
        gate.set()
        for r in (cut, beside, third):
            assert r.done.wait(timeout=180) and r.error is None
        # The primed token, two ticks, one step; then the EOS, cut off.
        assert cut.result.gen_tokens == 1 + 2 * STEPS + 1
        got = []
        while True:
            tok = streamed.get(timeout=5)
            if tok is None:
                break
            got.append(tok)
        assert got[-1] == engine.tokenizer.eos_id
        assert got[:-1] == cut.result.token_ids
        # Tick 3 (the 4th call) went ahead of tick 2's fetch, with the
        # slot that then ended.
        assert calls[3] == calls[2] + 1
        assert engine.ahead_dead_slot_steps_total == STEPS
        # ... and was fetched and emitted before slot 0 was given away:
        # launch 3 ahead, fetch/emit 2 (the EOS), fetch/emit 3, admit.
        eos = [i for i, e in enumerate(log) if e == "E"][2]
        assert log[eos - 2:eos + 4] == ["La", "F", "E", "F", "E", "A0"]
        assert beside.result.token_ids == _solo(PROMPTS[1], 24)
        assert third.result.token_ids == _solo(PROMPTS[2], 9)
    finally:
        engine.stop()


# -- (c) the four rules --------------------------------------------------------

def _free_slot(e):
    slot, e._slots[1] = e._slots[1], None
    return lambda: e._slots.__setitem__(1, slot)


def _budget_end(e):
    slot = e._slots[0]
    budget, slot.budget = slot.budget, len(slot.tokens) + STEPS
    return lambda: setattr(slot, "budget", budget)


def _span_end(e):
    pos = int(e._pos[1])
    e._pos[1] = e.cfg.max_seq_len - 1 - STEPS
    return lambda: e._pos.__setitem__(1, pos)


def _prefill_in_flight(e):
    e._prefill = object()
    return lambda: setattr(e, "_prefill", None)


def _dropped_carry(e):
    pos = e._carry.pop("pos")
    return lambda: e._carry.__setitem__("pos", pos)


def _blocks_missing(e):
    blocks = e._slots[0].blocks
    e._slots[0].blocks = []
    return lambda: setattr(e._slots[0], "blocks", blocks)


def _stopping(e):
    e._stop.set()
    return e._stop.clear


RULES = {
    "1 a free slot": _free_slot,
    "2 a budget end in the tick in flight": _budget_end,
    "2 the span's end in the tick in flight": _span_end,
    "3 a prefill in flight": _prefill_in_flight,
    "3 a stop": _stopping,
    "4 a dropped carry": _dropped_carry,
    "4 blocks the allocator did not give outright": _blocks_missing,
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_each_rule_alone_keeps_a_pass_on_the_settle_first_order(rule):
    """A full batch of two greedy requests, where every decision but the
    last says "ahead": with one rule broken at every decision (and
    nothing else: the unbroken predicate is asked first) every pass
    launches, fetches and emits in today's order, and the replies are
    the same."""
    asks = [(PROMPTS[0], 21, 0.0), (PROMPTS[1], 21, 0.0)]
    engine = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        real = engine._may_go_ahead
        whole, broken = [], []

        def deciding(tick):
            whole.append(real(tick))
            undo = RULES[rule](engine)
            try:
                broken.append(real(tick))
            finally:
                undo()
            return broken[-1]

        engine._may_go_ahead = deciding
        log = _log_order(engine)
        reqs = _serve(engine, asks)
        assert [r.result.token_ids for r in reqs] == [
            _solo(p, n) for p, n, _ in asks]
        # 20 tokens after the primed one: five ticks, the fifth ends both.
        assert whole == [True] * 4 + [False]
        assert broken == [False] * 5
        assert log == ["A0", "A1"] + ["L", "F", "E"] * 5
        assert engine.ticks_ahead_total == 0
    finally:
        engine.stop()


def test_a_known_end_is_settled_first_and_no_tick_is_wasted():
    """The unbroken order of the same run: four ticks go ahead, the one
    in which both budgets end is known to and is fetched before anything
    else is launched; one fetch a launch, none for a dead slot."""
    engine = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        log = _log_order(engine)
        reqs = _serve(engine, [(PROMPTS[0], 21, 0.0), (PROMPTS[1], 21, 0.0)])
        assert all(r.result.gen_tokens == 21 for r in reqs)
        assert log == (["A0", "A1", "L"] + ["La", "F", "E"] * 4 + ["F", "E"])
        stats = engine.tick_stats()
        assert stats["launched_total"] == 5 and stats["ahead_total"] == 4
        assert stats["ahead_dead_slot_steps_total"] == 0
    finally:
        engine.stop()


def test_a_free_slot_or_a_riding_chunk_never_goes_ahead():
    """Rule 1 as traffic makes it: one request on two slots, then a long
    prompt that is chunk-prefilled beside it (its slot stands reserved
    and empty until the prompt lands)."""
    tier = _tier(prefill_chunk_tokens=16, prefill_buckets=(16, 32, 64, 128))
    engine = ContinuousBatchingEngine(tier, seed=1)
    try:
        log = _log_order(engine)
        first = engine.submit(PROMPTS[0], max_new_tokens=40)
        deadline = time.monotonic() + 60
        while "E" not in log and time.monotonic() < deadline:
            time.sleep(0.001)
        long = engine.submit(" ".join(PROMPTS[1:3]), max_new_tokens=3)
        assert first.done.wait(180) and long.done.wait(180)
        assert first.error is None and long.error is None
        assert engine.prefill_stats()["chunks_total"] >= 2
        # Once it has landed it ends within its first tick (a known
        # end), and then its slot is free again.
        assert "La" not in log and engine.ticks_ahead_total == 0
    finally:
        engine.stop()


# -- (d) a fetch that raises ---------------------------------------------------

def test_a_fetch_that_raises_fails_the_slots_once_and_drops_the_tick_in_flight(
        monkeypatch):
    alone = _solo(PROMPTS[2], 9)
    engine = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        fetch = batching._fetch_tick
        fetched = []

        def fetching(x):
            fetched.append(engine.ticks_launched_total)
            if len(fetched) == 2:
                raise RuntimeError("fetch exploded")
            return fetch(x)

        monkeypatch.setattr(batching, "_fetch_tick", fetching)
        failed = []
        fail = engine._fail_slot

        def failing(ix, exc):
            if engine._slots[ix] is not None:
                failed.append(ix)
            fail(ix, exc)

        engine._fail_slot = failing
        reqs = _serve(engine, [(PROMPTS[0], 24, 0.0), (PROMPTS[1], 24, 0.0)])
        assert [str(r.error) for r in reqs] == ["fetch exploded"] * 2
        assert sorted(failed) == [0, 1]
        # The second fetch had a third tick in flight behind it: never
        # fetched, and nothing of it left for the next to trust.
        assert fetched == [2, 3] and engine.ticks_launched_total == 3
        assert "pos" not in engine._carry and "cur" not in engine._carry
        assert engine._slots == [None, None]
        uploads = dict(engine.prepare_uploads_total)
        ok = engine.generate(PROMPTS[2], max_new_tokens=9)
        assert ok.token_ids == alone
        assert engine.prepare_uploads_total["pos"] == uploads["pos"] + 1
        assert engine.prepare_uploads_total["cur"] == uploads["cur"] + 1
        assert fetched[2:] == [4, 5]         # one fetch a tick again
    finally:
        engine.stop()


# -- (e) what /stats and /metrics say ------------------------------------------

def test_stats_and_the_counter_read_what_the_passes_did():
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.utils.telemetry import engine_stats
    m = get_observability().m
    tier = dataclasses.replace(_tier(), name="ahead_probe")
    engine = ContinuousBatchingEngine(tier, seed=3)
    try:
        log = _log_order(engine)
        t0 = time.perf_counter()
        _serve(engine, [(PROMPTS[0], 21, 0.0), (PROMPTS[1], 13, 0.0)])
        wall_ms = (time.perf_counter() - t0) * 1000.0
        tick = engine_stats(engine)["tick"]
        launched = sum(1 for e in log if e in ("L", "La"))
        ahead = log.count("La")
        # Three ticks with both (two ahead, the shorter one's end known),
        # then two with a free slot.
        assert (launched, ahead) == (5, 2)
        assert tick["launched_total"] == launched
        assert tick["ahead_total"] == ahead
        assert tick["ahead_share"] == round(ahead / launched, 4)
        assert tick["ahead_dead_slot_steps_total"] == 0
        assert m.decode_ticks_ahead.labels("ahead_probe").value == ahead
        # A tick's time is its own: counted from the fetch before it
        # where it was dispatched ahead of that, so the ticks add up to
        # no more than the wall they ran in.
        assert len(engine.tick_ms) == launched
        assert sum(engine.tick_ms) <= wall_ms
    finally:
        engine.stop()


def test_an_engine_that_never_filled_its_batch_reads_zero():
    engine = ContinuousBatchingEngine(_tier(), seed=3)
    try:
        assert engine.tick_stats()["ahead_share"] is None
        engine.generate(PROMPTS[0], max_new_tokens=9)
        tick = engine.tick_stats()
        assert tick["ahead_total"] == 0 and tick["ahead_share"] == 0.0
    finally:
        engine.stop()


def test_a_tick_ahead_has_its_decode_slice_from_its_launch_to_its_fetch():
    """``decode`` is a tick from its launch to the return of its fetch in
    either order: every record has one, its ``fetch`` inside it, and
    behind a tick dispatched ahead it begins before its record does
    (in the pass before), which is what lets a device trace's executions
    be laid into the slices one to one."""
    engine = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        _serve(engine, [(PROMPTS[0], 21, 0.0), (PROMPTS[1], 21, 0.0)])
        deadline = time.monotonic() + 10
        while engine.profiler._t0 is not None and time.monotonic() < deadline:
            time.sleep(0.001)
        ticks = [r for r in engine.profiler.records() if r["slots"]]
        assert len(ticks) == 5
        starts = []
        for rec in ticks:
            spans = {}
            for name, rel, dur, *_ in rec["spans"]:
                spans.setdefault(name, []).append((rel, rel + dur))
            (decode,), (fetch,) = spans["decode"], spans["fetch"]
            assert decode[0] - 1e-6 <= fetch[0] and fetch[1] <= decode[1] + 1e-6
            starts.append(decode[0])
            assert all(decode[0] - 1e-6 <= a and b <= decode[1] + 1e-6
                       for a, b in spans.get("dispatch", ()))
        assert starts[0] > 0 and all(s < 0 for s in starts[1:])
        assert np.isfinite(engine.profiler.phase_stats()["coverage"])
    finally:
        engine.stop()
