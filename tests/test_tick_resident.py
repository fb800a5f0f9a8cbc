"""The decode tick's small inputs stay on the device (PR 36).

The tick returns what the next tick starts from (``pos``, ``cur`` and
the engine's key, split inside the program); the engine hands those back
and uploads a mirror only after a writer other than the plain emit has
touched it; growth for the next tick and its table rung ride in the
running tick's shadow.  The host mirrors stay the authority: every check
here compares what a tick was GIVEN with the mirrors at that moment.
"""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from distributed_llm_tpu.config import TierConfig
from distributed_llm_tpu.engine.batching import (ContinuousBatchingEngine,
                                                 _sample_batched)
from distributed_llm_tpu.engine.paged_kv import TRASH_BLOCK, decode_step_paged

STEPS = 4
PROBE_A = "tell me about rivers and lakes and streams and oceans please"
PROBE_B = "what is the tallest mountain on the continent of asia today"


def _tier(**kw):
    defaults = dict(name="nano", model_preset="nano_test", max_new_tokens=24,
                    prefill_buckets=(16, 32, 64), decode_batch=2,
                    kv_block_size=16, decode_steps_per_tick=STEPS)
    defaults.update(kw)
    return TierConfig(**defaults)


# Flow 17/18/20 of the verify notes: what each family serves with.
_FAMILY = dict(decode_batch=4, prefill_buckets=(16, 32, 64, 128),
               prefill_chunk_tokens=16)
FAMILIES = {
    "dense": _tier(),
    "latent": _tier(model_preset="latent_test", **_FAMILY),
    "hybrid": _tier(model_preset="hybrid_test", enable_prefix_cache=False,
                    **_FAMILY),
    "shared_kv": _tier(model_preset="shared_kv_test",
                       enable_prefix_cache=False, **_FAMILY),
}


class TickSpy:
    """Stands where the engine keeps its compiled tick.  Every call is
    checked against the mirrors as they stand at the launch (the
    scheduler's own thread, so nothing moves under the check), and
    recorded: whether ``pos``/``cur`` were the very arrays the tick
    before returned, and what ``prepare`` uploaded for it."""

    def __init__(self, engine, edit=None, before=None):
        self.engine = engine
        self.real = engine._decode_step()
        self.edit = edit            # (spy, toks) -> toks
        self.before = before        # (spy) -> None, may raise
        self.calls = []
        self.faults = []
        self._last = (None, None)
        self._uploads = dict(engine.prepare_uploads_total)
        self._ahead = engine.ticks_ahead_total
        engine._decode_fn = self

    def __call__(self, params, pool, tables, pos, cur, temps, key):
        e = self.engine
        now = dict(e.prepare_uploads_total)
        uploads = {k: v - self._uploads.get(k, 0) for k, v in now.items()
                   if v != self._uploads.get(k, 0)}
        self._uploads = now
        # A tick dispatched ahead of the fetch before it (PR 52) starts
        # where the tick in flight ends: the mirrors stand its steps
        # behind, and its ``cur`` is no mirror's yet, only that tick's.
        ahead = e.ticks_ahead_total != self._ahead
        self._ahead = e.ticks_ahead_total
        call = {"resident": pos is self._last[0] and cur is self._last[1],
                "uploads": uploads, "ahead": ahead,
                "live": [s is not None for s in e._slots]}
        if ahead and not (call["resident"] and all(call["live"])):
            self.faults.append((len(self.calls), "ahead", call))
        checks = [("pos", pos, e._pos + STEPS * ahead),
                  ("temps", temps, e._temps)]
        if not ahead:
            checks.append(("cur", cur, e._cur))
        for name, given, mirror in checks:
            if not np.array_equal(np.asarray(given), mirror):
                self.faults.append((len(self.calls), name,
                                    np.asarray(given).tolist(),
                                    mirror.tolist()))
        for ix, live in enumerate(call["live"]):
            if not live and (e._pos[ix], e._cur[ix],
                             e._tables[ix, 0]) != (0, 0, TRASH_BLOCK):
                self.faults.append((len(self.calls), "free slot", ix))
        self.calls.append(call)
        if self.before is not None:
            self.before(self)
        (toks, pos2, cur2, key2), pool2 = self.real(
            params, pool, tables, pos, cur, temps, key)
        self._last = (pos2, cur2)
        if self.edit is not None:
            toks = self.edit(self, toks)
        return (toks, pos2, cur2, key2), pool2


def _run(engine, prompts, **kw):
    """``prompts`` at once, each on a thread; results in order."""
    out = [None] * len(prompts)

    def one(i, p):
        out[i] = engine.generate(p, **kw)

    threads = [threading.Thread(target=one, args=(i, p))
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
        time.sleep(0.02)
    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads)
    return out


def test_quiet_ticks_take_everything_from_the_tick_before():
    """One request, 24 tokens: after its first tick every tick is quiet.
    The resident ``pos``/``cur`` are the mirrors on every slot (the free
    one stays at position 0, token 0, on the trash block), no ``prepare``
    upload is counted, and /stats says so."""
    engine = ContinuousBatchingEngine(_tier(), seed=3)
    try:
        spy = TickSpy(engine)
        r = engine.generate(PROBE_A, max_new_tokens=24)
        assert r.gen_tokens == 24
        assert spy.faults == []
        assert len(spy.calls) == 6           # 23 tokens of 4 a tick
        first, quiet = spy.calls[0], spy.calls[1:]
        assert not first["resident"]
        assert {"pos", "cur", "temps", "tables"} <= set(first["uploads"])
        for call in quiet:
            assert call["resident"] and call["uploads"] == {}
            assert call["live"] == [True, False]
        # The first block holds the 16-token bucket; growth came in the
        # shadow each time, so no quiet tick uploaded a table either.
        stats = engine.tick_stats()
        assert stats["launched_total"] == 6
        assert stats["resident_total"] == 5
        assert stats["resident_share"] == round(5 / 6, 4)
        assert stats["prepare_uploads"]["pos"] == 1
    finally:
        engine.stop()


def _second_request(engine, spy, at, prompt, **kw):
    """``prompt`` as a second request that joins a running one between
    tick ``at`` and the next: that tick's launch waits until it is
    queued, and it is queued once the tick before has been seen."""
    def before(s):
        if len(s.calls) - 1 == at:
            deadline = time.monotonic() + 30
            while not engine.queue_depth() and time.monotonic() < deadline:
                time.sleep(0.001)
    spy.before = before
    deadline = time.monotonic() + 60
    while len(spy.calls) < at and time.monotonic() < deadline:
        time.sleep(0.001)
    return engine.generate(prompt, **kw)


def _live_changes(spy, sign):
    """Ticks launched with more (``sign`` 1) or fewer (-1) live slots
    than the tick before."""
    return [i for i in range(1, len(spy.calls))
            if (sum(spy.calls[i]["live"])
                - sum(spy.calls[i - 1]["live"])) * sign > 0]


def _eos_at(engine, call_ix, slot_ix, step):
    def edit(spy, toks):
        if len(spy.calls) - 1 == call_ix:
            toks = toks.at[step, slot_ix].set(engine.tokenizer.eos_id)
        return toks
    return edit


# Each writer that is not the plain emit: the tick after it must be fed
# from the mirrors (an upload of ``pos`` and ``cur`` is counted, and the
# spy's comparison of every given array with its mirror finds nothing).
def _go_live(engine):
    spy = TickSpy(engine)
    first = engine.submit(PROBE_A)
    second = _second_request(engine, spy, 2, PROBE_B, max_new_tokens=9)
    assert first.done.wait(60) and second.gen_tokens == 9
    return spy, _live_changes(spy, 1)


def _end_at_cap(engine):
    spy = TickSpy(engine)
    long = engine.submit(PROBE_A)
    short = _second_request(engine, spy, 1, PROBE_B, max_new_tokens=5)
    assert long.done.wait(60) and long.result.gen_tokens == 24
    assert short.gen_tokens == 5            # the primed token and a tick
    return spy, _live_changes(spy, -1)


def _eos_mid_tick(engine):
    spy = TickSpy(engine, edit=_eos_at(engine, 2, 0, 1))
    r = engine.generate(PROBE_A, max_new_tokens=24)
    # The primed token, two ticks of four, one more, then the EOS: cut.
    assert r.gen_tokens == 1 + 2 * STEPS + 1
    again = engine.generate(PROBE_B, max_new_tokens=6)
    assert again.gen_tokens == 6
    return spy, [3]


def _preemption(engine):
    spy = TickSpy(engine)
    elder = engine.submit(PROBE_A)
    younger = _second_request(engine, spy, 1, PROBE_B)
    assert elder.done.wait(60) and engine.preempted_total >= 1
    assert elder.result.gen_tokens == younger.gen_tokens == 24
    return spy, _live_changes(spy, -1)[:1]


def _settled_chunked_prefill(engine):
    spy = TickSpy(engine)
    first = engine.submit(PROBE_A)
    long = _second_request(engine, spy, 1, " ".join([PROBE_B] * 2),
                           max_new_tokens=6)
    assert first.done.wait(60) and long.gen_tokens == 6
    assert engine.prefill_stats()["chunks_total"] >= 2
    return spy, _live_changes(spy, 1)


def _tick_that_raised(engine):
    def before(spy):
        if len(spy.calls) == 3:
            raise RuntimeError("tick exploded")
    spy = TickSpy(engine, before=before)
    with pytest.raises(RuntimeError, match="tick exploded"):
        engine.generate(PROBE_A, max_new_tokens=24)
    assert engine._carry.get("pos") is None
    ok = engine.generate(PROBE_B, max_new_tokens=6)
    assert ok.gen_tokens == 6
    return spy, [3]


def _speculative_round(engine):
    """A self-draft accepts everything, so every round is speculative
    and no plain tick runs: the round uploads, and leaves nothing
    behind for a tick to trust."""
    spy = TickSpy(engine)
    seen = []
    emit = engine._emit_spec

    def emitting(*args):
        seen.append(set(engine._carry))     # what the round was fed
        emit(*args)
        seen.append(set(engine._carry))     # what it left

    engine._emit_spec = emitting
    r = engine.generate(PROBE_A, max_new_tokens=20)
    assert r.gen_tokens == 20 and engine.spec_stats()["drafted_total"] > 0
    assert seen and all(fed >= {"pos", "cur", "temps"} and "pos" not in left
                        and "cur" not in left
                        for fed, left in zip(seen[::2], seen[1::2]))
    uploads = engine.tick_stats()["prepare_uploads"]
    assert uploads["pos"] == uploads["cur"] == len(seen) // 2
    assert engine.tick_stats()["resident_total"] == 0
    return spy, None


WRITERS = {
    "a slot going live": (_go_live, {}),
    "an end at the cap": (_end_at_cap, {}),
    "an end of sequence in the middle of a tick": (_eos_mid_tick, {}),
    "a preemption": (_preemption, dict(kv_pool_blocks=5,
                                       enable_prefix_cache=False)),
    "a settled chunked prefill": (_settled_chunked_prefill, dict(
        prefill_chunk_tokens=16, prefill_buckets=(16, 32, 64, 128))),
    "a tick that raised": (_tick_that_raised, {}),
    "a speculative round": (_speculative_round, dict(
        spec_decode=True, draft_preset="nano_test", decode_batch=1)),
}


@pytest.mark.parametrize("writer", sorted(WRITERS))
def test_a_writer_drops_the_carry_and_the_next_tick_runs_from_the_mirrors(
        writer):
    scenario, tier_kw = WRITERS[writer]
    engine = ContinuousBatchingEngine(_tier(**tier_kw), seed=1)
    try:
        spy, after = scenario(engine)
        assert spy.faults == []
        if after is None:
            return                  # no plain tick ran: checked inside
        assert after, "the scenario did not produce its event"
        for ix in after:
            call = spy.calls[ix]
            assert not call["resident"], (writer, ix)
            assert {"pos", "cur"} <= set(call["uploads"]), (writer, call)
        # And ticks with nothing between them stayed resident.
        assert any(c["resident"] for c in spy.calls)
        assert all(c["resident"] == (not c["uploads"].keys()
                                     & {"pos", "cur"})
                   for c in spy.calls[1:])
    finally:
        engine.stop()


def test_a_reply_cut_in_the_middle_of_a_tick_leaves_the_other_untouched():
    """The slot beside one that ends mid-tick goes on from the mirrors:
    its reply is what it is alone."""
    solo = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        alone = solo.generate(PROBE_B, max_new_tokens=24).token_ids
    finally:
        solo.stop()
    engine = ContinuousBatchingEngine(_tier(), seed=1)
    try:
        spy = TickSpy(engine)
        spy.edit = lambda s, toks: (
            toks.at[2, 0].set(engine.tokenizer.eos_id)
            if s.calls[-1]["live"] == [True, True]
            and not any(c["live"] == [True, True] for c in s.calls[:-1])
            else toks)
        cut, whole = _run(engine, [PROBE_A, PROBE_B], max_new_tokens=24)
        assert cut.gen_tokens < 24
        assert whole.token_ids == alone
        assert spy.faults == []
    finally:
        engine.stop()


def test_growth_in_the_shadow_returns_every_block_of_a_slot_that_ends_there():
    """A slot that ends in the very tick whose shadow grew it frees the
    new block with its others (conftest arms DLLM_KV_LEAK_CHECK: stop()
    asserts an empty pool as well)."""
    engine = ContinuousBatchingEngine(_tier(enable_prefix_cache=False),
                                      seed=1)
    total = engine.allocator.available
    grown = []
    ahead = engine._prepare_ahead

    def watched(active):
        before = {ix: len(engine._slots[ix].blocks) for ix in active}
        ahead(active)
        grown.append(any(len(engine._slots[ix].blocks) > n
                         for ix, n in before.items()))

    engine._prepare_ahead = watched
    try:
        spy = TickSpy(engine)
        spy.edit = lambda s, toks: (
            toks.at[0, 0].set(engine.tokenizer.eos_id)
            if engine._blocks_needed(0, engine._slots[0], 2 * STEPS)
            > len(engine._slots[0].blocks) else toks)
        r = engine.generate(PROBE_A, max_new_tokens=40)
        assert r.gen_tokens < 40
        # The edit runs before the shadow: the tick it cut is the last,
        # and that tick's shadow took a block.
        assert grown and grown[-1] and not any(grown[:-1])
        assert engine.allocator.available == total
    finally:
        engine.stop()
    assert engine.allocator.available == total


def test_a_dry_pool_in_the_shadow_falls_back_to_the_pass_own_growth():
    """With nothing free the shadow takes nothing: it neither evicts nor
    preempts.  The next pass grows the slot as before, by evicting the
    parked prefix, and the reply is what an ample pool gives."""
    tier = _tier(decode_batch=1, max_new_tokens=60)
    ample = ContinuousBatchingEngine(tier, seed=1)
    try:
        want = ample.generate(PROBE_B).token_ids
    finally:
        ample.stop()
    engine = ContinuousBatchingEngine(
        dataclasses.replace(tier, kv_pool_blocks=5), seed=1)
    try:
        engine.generate(PROBE_A, max_new_tokens=2)      # parks 2 of 5
        assert engine.prefix_cache.stats()["entries"] == 1
        dry = []
        ahead = engine._prepare_ahead

        def watched(active, in_flight=1):
            # One slot is a full batch: behind a tick dispatched ahead
            # (PR 52) the shadow looks a tick further.
            slot = engine._slots[active[0]]
            short = (engine._blocks_needed(active[0], slot,
                                           (in_flight + 1) * STEPS)
                     > len(slot.blocks))
            free = engine.allocator.available
            n = len(slot.blocks)
            ahead(active, in_flight)
            if short and not free:
                dry.append(len(slot.blocks) == n)

        engine._prepare_ahead = watched
        spy = TickSpy(engine)
        got = engine.generate(PROBE_B)
        assert dry and all(dry), "the shadow met no dry pool"
        assert got.token_ids == want and got.gen_tokens == 60
        assert spy.faults == []
        # The pass's own growth changed a row with the device idle.
        assert any("tables" in c["uploads"] for c in spy.calls[1:])
    finally:
        engine.stop()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_greedy_replies_are_what_a_tick_fed_from_the_mirrors_gives(family):
    """The parent's data flow, kept as the control: an engine whose
    carry is dropped after every tick uploads the mirrors before each
    one.  Two overlapping greedy requests: token for token the same."""
    tier = FAMILIES[family]
    replies = {}
    for fed_from in ("device", "mirrors"):
        engine = ContinuousBatchingEngine(tier, seed=2)
        try:
            spy = TickSpy(engine)
            if fed_from == "mirrors":
                ahead = engine._prepare_ahead
                engine._prepare_ahead = lambda active, e=engine, f=ahead: (
                    f(active), e._drop_carry(temps=True))
            replies[fed_from] = [r.token_ids for r in _run(
                engine, [PROBE_A, PROBE_B[:40]], max_new_tokens=20)]
            assert spy.faults == []
            resident = [c["resident"] for c in spy.calls[1:]]
            assert (any(resident) if fed_from == "device"
                    else not any(resident))
        finally:
            engine.stop()
    assert replies["device"] == replies["mirrors"]
    assert all(len(ids) == 20 for ids in replies["device"])


def test_the_key_stream_is_a_chain_of_plain_splits():
    """A seeded engine at temperature 0.8: the key each tick is given,
    and the tokens it samples, are those of ``jax.random.split`` calls
    written out here in the parent's order: one split an admission,
    between the ticks' own; inside a tick one split a step."""
    seed = 7
    engine = ContinuousBatchingEngine(_tier(decode_batch=1), seed=seed)
    chain = {"key": jax.random.PRNGKey(seed ^ 0xBA7C4), "checked": 0}
    admit = engine._admit

    def admitting(req, ix):
        done = admit(req, ix)
        if done:
            chain["key"], _ = jax.random.split(chain["key"])
        return done

    engine._admit = admitting
    real = engine._decode_step()
    sampled = []

    def tick(params, pool, tables, pos, cur, temps, key):
        assert np.array_equal(np.asarray(key), np.asarray(chain["key"]))
        chain["key"], r = jax.random.split(chain["key"])
        want = []
        p, c, kv = pos, cur, pool
        for _ in range(STEPS):
            logits, kv = decode_step_paged(engine.cfg, params, c, p, kv,
                                           tables)
            r, sub = jax.random.split(r)
            c = _sample_batched(logits, sub, temps)
            p = p + 1
            want.append(np.asarray(c))
        out = real(params, pool, tables, pos, cur, temps, key)
        (toks, _, _, key_out), _ = out
        assert np.array_equal(np.asarray(toks), np.stack(want))
        assert np.array_equal(np.asarray(key_out), np.asarray(chain["key"]))
        chain["checked"] += 1
        sampled.append(np.asarray(toks)[:, 0])
        return out

    engine._decode_fn = tick
    try:
        first = engine.generate(PROBE_A, max_new_tokens=13,
                                temperature=0.8)
        assert chain["checked"] == 3
        assert first.token_ids[1:] == np.concatenate(sampled).tolist()
        del sampled[:]
        second = engine.generate(PROBE_B, max_new_tokens=9,
                                 temperature=0.8)
        assert chain["checked"] == 5
        assert second.token_ids[1:] == np.concatenate(sampled).tolist()
        # Sampled, not greedy: the temperature reached the tick.
        greedy = engine.generate(PROBE_B, max_new_tokens=9).token_ids
        assert greedy != second.token_ids
    finally:
        engine.stop()


def test_stats_and_metrics_carry_the_resident_share():
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.utils.telemetry import engine_stats
    m = get_observability().m
    tier = dataclasses.replace(_tier(), name="resident_probe")
    engine = ContinuousBatchingEngine(tier, seed=3)
    try:
        engine.generate(PROBE_A, max_new_tokens=24)
        tick = engine_stats(engine)["tick"]
        assert tick["launched_total"] == 6 and tick["resident_total"] == 5
        assert tick["resident_share"] == round(5 / 6, 4)
        for what in ("pos", "cur", "temps", "tables"):
            assert tick["prepare_uploads"][what] == 1
            assert m.tick_prepare_uploads.labels(
                "resident_probe", what).value == 1
    finally:
        engine.stop()


@pytest.mark.parametrize("impl,want", [("auto", "merged"),
                                       ("pallas", "streamed")])
def test_stats_name_the_attention_form_of_every_warmed_rung(monkeypatch,
                                                            impl, want):
    """GET /stats ``tiers.<tier>.tick.attention_form``: the form each
    compiled rung of the tick was traced with, by its table window in
    tokens — ``ops.attention.decode_form``'s answer for the shapes the
    tick sees, the rule the dispatching op itself follows.  On the CPU
    an engine takes the XLA form (``merged``); one that opted into
    kernels, as every unsharded engine on the chip does, gets the kernel
    that walks the block table (``streamed``) where every query head has
    a K/V head of its own and the rows fill whole lanes."""
    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.utils.telemetry import engine_stats
    base = _tier()
    cfg = MODEL_PRESETS[base.model_preset]
    # A K/V head to every query head, rows of 128 lanes: what the rule
    # asks of a window.
    monkeypatch.setitem(MODEL_PRESETS, "form_probe", dataclasses.replace(
        cfg, num_heads=4, num_kv_heads=4, hidden_size=128,
        attention_impl=impl))
    monkeypatch.delenv("DLLM_ATTENTION", raising=False)
    tier = dataclasses.replace(base, name="form_probe",
                               model_preset="form_probe")
    engine = ContinuousBatchingEngine(tier, seed=3)
    try:
        out = engine.generate(PROBE_A, max_new_tokens=8)
        assert len(out.token_ids) == 8
        forms = engine_stats(engine)["tick"]["attention_form"]
        bs = engine.paged.block_size
        assert forms == {str(wb * bs): want
                         for wb, _ in engine._compiled["decode"]}
        assert forms and engine.decode_attention_form() == want
        assert engine_stats(engine)["decode_attention"] == want
    finally:
        engine.stop()
