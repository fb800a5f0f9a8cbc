"""One host timeline on the device's clock (ISSUE 26): named step
programs and scopes, trace annotations paired with every profiler
stamp, the closed tick phases, the exact counters
(``dllm_tick_phase_ms_total``, ``dllm_prefill_wait_ms``,
``dllm_prefill_lane_wait_ms``, ``dllm_first_delta_hold_ms``) and
``GET /debug/trace``'s window and clock origin.

No real ``jax.profiler`` capture runs here: a recorder stands in for
``TraceAnnotation``."""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_tpu.config import tiny_batched_cluster, tiny_cluster
from distributed_llm_tpu.obs import Observability
from distributed_llm_tpu.obs import profiler as P
from distributed_llm_tpu.obs.spans import RequestTrace, use_trace

LONG_Q = ("user: tell me about rivers lakes mountains oceans deltas "
          "streams glaciers valleys canyons plateaus islands forests")

# The phase names ``tick_phases`` carried at the commit before ISSUE 26
# (obs/profiler.py PHASES there), written out: the benchmark's
# sched.host_ms_per_tick sums what is published under these.
PARENT_PHASES = ("admit", "prefill", "cow_copy", "table_upload", "decode",
                 "draft", "verify", "emit", "chunk_prefill", "demote",
                 "promote")


# -- A. names -----------------------------------------------------------------

@pytest.fixture(scope="module")
def spec_engine():
    """A tiny engine with a draft model, so all seven step-function
    builders can be asked for their program (nothing is compiled: a
    jitted function has its name before its first call)."""
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    tier = dataclasses.replace(tiny_batched_cluster().nano,
                               spec_decode=True, draft_preset="nano_test")
    eng = ContinuousBatchingEngine(tier, seed=7)
    try:
        yield eng
    finally:
        eng.stop()


@pytest.mark.parametrize("name,build", [
    ("decode_tick", lambda e: e._decode_step()),
    ("chunk_prefill", lambda e: e._chunk_prefill_fn(16, 32)),
    ("cold_prefill", lambda e: e._prefill_fn(16)),
    ("spec_draft", lambda e: e._spec_draft_fn(2)),
    ("spec_verify", lambda e: e._spec_verify_fn(2)),
    ("draft_prefill", lambda e: e._draft_prefill_fn(16)),
    ("draft_chunk", lambda e: e._draft_chunk_fn(16, 32)),
])
def test_each_step_program_has_its_own_name(spec_engine, name, build):
    """The trace's "XLA Modules" line reads ``jit_<name>``: the
    benchmark's by-name readers find a program whatever its loops look
    like."""
    assert build(spec_engine).__name__ == name


def test_block_programs_keep_their_names(spec_engine):
    assert spec_engine._cow_copy_fn().__name__ == "copy_block"
    assert spec_engine._writer_fn(1).__name__ == "write_prefill_blocks"
    assert spec_engine._spill_gather_fn().__name__ == "gather_blocks"
    assert spec_engine._spill_write_fn().__name__ == "scatter_blocks"


def test_paged_step_carries_named_scopes():
    """``kv_write``, ``kv_gather``, ``attention``, ``ffn`` and
    ``sample`` are in the compiled decode step's ``op_name``s (what
    XProf shows as an op's name stack); metadata only."""
    from distributed_llm_tpu import models
    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.engine.batching import _sample_batched
    from distributed_llm_tpu.engine.paged_kv import (PagedConfig,
                                                     decode_step_paged,
                                                     init_pool)
    cfg = MODEL_PRESETS["nano_test"]
    paged = PagedConfig(block_size=16, max_slots=2, max_seq_len=32)
    params = jax.eval_shape(
        lambda: models.init_params(cfg, 0))
    pool = jax.eval_shape(lambda: init_pool(cfg, paged, "none"))

    def step(params, pool, tables, pos, cur, rng):
        logits, pool = decode_step_paged(cfg, params, cur, pos, pool, tables)
        return _sample_batched(logits, rng, jnp.zeros((2,))), pool

    hlo = jax.jit(step).lower(
        params, pool, jax.ShapeDtypeStruct((2, 2), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    for scope in ("kv_write", "attention/kv_gather", "attention", "ffn",
                  "sample"):
        assert re.search(rf'op_name="jit\(step\)/[^"]*{scope}/', hlo), scope


# -- B. annotations -----------------------------------------------------------

class _Recorder:
    """In place of ``jax.profiler.TraceAnnotation``: notes every enter
    and exit."""

    log = []

    def __init__(self, label):
        self.label = label

    def __enter__(self):
        _Recorder.log.append(("enter", self.label))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("exit", self.label))


def test_every_stamp_enters_and_exits_one_annotation():
    _Recorder.log = []
    prof = P.TickProfiler("nano", capacity=16, annotation=_Recorder)
    with prof.phase("prepare"):
        with prof.phase("table_upload"):
            pass
    with prof.phase("decode"):
        with prof.phase("dispatch"):
            pass
        with prof.phase("fetch"):
            pass
    prof.commit(1)
    with prof.idle_wait():
        pass
    assert _Recorder.log == [
        ("enter", "dllm.nano.prepare"), ("enter", "dllm.nano.table_upload"),
        ("exit", "dllm.nano.table_upload"), ("exit", "dllm.nano.prepare"),
        ("enter", "dllm.nano.decode"), ("enter", "dllm.nano.dispatch"),
        ("exit", "dllm.nano.dispatch"), ("enter", "dllm.nano.fetch"),
        ("exit", "dllm.nano.fetch"), ("exit", "dllm.nano.decode"),
        ("enter", "dllm.nano.idle_wait"), ("exit", "dllm.nano.idle_wait")]
    # One annotation per stamp: six ring spans and the idle wait.
    (rec,) = prof.records()
    assert len(rec["spans"]) == 5
    assert len(_Recorder.log) == 2 * (len(rec["spans"]) + 1)


def test_idle_wait_is_a_total_and_never_a_record():
    """An idle engine must not flush its ring at 20 Hz."""
    prof = P.TickProfiler("nano", capacity=16, annotation=_Recorder)
    for _ in range(50):
        with prof.idle_wait():
            pass
        prof.commit(0)
    assert prof.records() == []
    assert prof.phase_stats()["totals"]["idle_wait"]["n"] == 50
    assert prof.self_totals()["idle_wait"] >= 0.0
    assert P.NULL_PROFILER.idle_wait() is P.NULL_PROFILER.phase("x")


def test_default_ring_holds_120_s_at_30_passes_a_second():
    assert P.DEFAULT_CAPACITY >= 120 * 30
    assert P.TickProfiler("t").capacity == P.DEFAULT_CAPACITY


# -- B. the closed phases on a real tiny engine -------------------------------

@pytest.fixture(scope="module")
def timeline_engine():
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(tiny_batched_cluster().nano, seed=3)
    try:
        reqs = [eng.submit(f"timeline question {i}", max_new_tokens=8)
                for i in range(4)]
        for r in reqs:
            assert r.done.wait(timeout=120) and r.error is None
        yield eng
    finally:
        eng.stop()


def _enclosed(rec, child, parent):
    """Every ``child`` span of a tick record lies inside one of its
    ``parent`` spans."""
    spans = rec["spans"]
    parents = [(rel, rel + dur) for n, rel, dur, *_ in spans if n == parent]
    kids = [(rel, rel + dur) for n, rel, dur, *_ in spans if n == child]
    return all(any(a - 1e-6 <= lo and hi <= b + 1e-6 for a, b in parents)
               for lo, hi in kids), kids


def test_new_phases_nest_and_self_times_cover_the_tick(timeline_engine):
    st = timeline_engine.profiler.phase_stats()
    assert {"admit", "prepare", "decode", "dispatch", "fetch", "account",
            "emit"} <= set(st["phases"])
    assert st["coverage"] >= 0.95, st
    ticks = [r for r in timeline_engine.profiler.records() if r["slots"]]
    assert ticks
    seen = 0
    for rec in ticks:
        for child, parent in (("dispatch", "decode"), ("fetch", "decode"),
                              ("table_upload", "prepare")):
            ok, kids = _enclosed(rec, child, parent)
            assert ok, (child, parent, rec)
            seen += len(kids)
        order = [n for n, *_ in sorted(rec["spans"], key=lambda s: s[1])
                 if n in ("decode", "account", "emit")]
        assert order == ["decode", "account", "emit"], rec
    assert seen >= 2 * len(ticks)
    # decode's time is all in its children.
    assert st["phases"]["decode"]["p50_ms"] < 0.25 * (
        st["phases"]["decode"]["dur_p50_ms"])


def test_tick_phases_keep_exactly_the_parents_names(timeline_engine):
    assert P.SAMPLED_PHASES == PARENT_PHASES
    sampled = timeline_engine.profiler.sampled_phases(last=128)
    names = set(sampled["tick_phases"])
    assert names <= set(PARENT_PHASES)
    assert {"admit", "decode", "emit"} <= names
    assert not names & {"prepare", "dispatch", "fetch", "account",
                        "idle_wait"}
    st = timeline_engine.profiler.phase_stats(last=128)["phases"]
    # decode is published at its full duration: what its self-time was
    # before it had children.
    assert sampled["tick_phases"]["decode"] == st["decode"]["dur_p50_ms"]
    assert sampled["tick_phases"]["decode"] >= st["fetch"]["p50_ms"]
    assert sampled["tick_phases"]["emit"] == st["emit"]["p50_ms"]
    # The router's collect hands the sampler exactly this.
    from distributed_llm_tpu.serving.router import Router
    collected = Router._collect_engine_state(timeline_engine)
    assert set(collected["tick_phases"]) == names


def test_decode_total_is_the_full_duration(timeline_engine):
    """``total_ms("decode")`` stays the conservation denominator: the
    whole tick, dispatch and fetch included."""
    prof = timeline_engine.profiler
    totals = prof.self_totals()
    assert prof.total_ms("decode") == pytest.approx(
        totals["decode"] + totals["dispatch"] + totals["fetch"], rel=1e-6)
    assert prof.total_ms("decode") == pytest.approx(
        sum(timeline_engine.tick_ms) if len(timeline_engine.tick_ms) < 512
        else prof.total_ms("decode"), rel=0.05)


def test_roofline_estimate_is_counted_on_the_tick_computed_on_demand(
        timeline_engine):
    """E: the tick only counts (kind, window, slots); ``work`` is
    computed when asked and gives what per-tick calls would have."""
    from distributed_llm_tpu.utils import roofline
    eng = timeline_engine
    assert eng._tick_work and all(
        k[0] == eng._tick_kind for k in eng._tick_work)
    want = {}
    for (_, window, batch, gb), (ticks, kv_sum) in eng._tick_work.items():
        assert gb is None
        part = roofline.decode_work(
            eng.cfg, eng.steps_per_tick, window, batch=batch,
            wbytes=eng._wbytes, kv_quantize=eng.tier.kv_quantize,
            kv_ctx=kv_sum / ticks)
        for k, v in part.items():
            want[k] = want.get(k, 0.0) + v * ticks
    got = eng.phases.work_summary()["decode"]
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-6)
    n_ticks = sum(acc[0] for acc in eng._tick_work.values())
    assert eng.phases.summary()["decode"]["count"] == n_ticks
    assert got["seconds"] == pytest.approx(sum(eng.tick_ms) / 1000.0,
                                           rel=0.01)


# -- C. counters ---------------------------------------------------------------

@pytest.fixture(scope="module")
def timeline_app():
    from distributed_llm_tpu.serving.app import create_app
    from distributed_llm_tpu.serving.router import Router
    obs = Observability(slow_ms=None)
    router = Router(strategy="heuristic", benchmark_mode=True,
                    cluster=tiny_batched_cluster(), observability=obs)
    client = create_app(router=router).test_client()
    for i in range(3):
        resp = client.post("/chat", json={
            "message": f"hi timeline {i}", "strategy": "heuristic",
            "session_id": f"tl{i}"})
        assert resp.status_code == 200
    yield client, router, obs
    router.drain()


def _phase_counter(obs):
    fam = obs.metrics.get("dllm_tick_phase_ms_total")
    return {key: child.value for key, child in fam.children().items()}


def test_tick_phase_counter_equals_the_profilers_totals(timeline_app):
    client, router, obs = timeline_app
    engine = router.tiers["nano"].server_manager.engine()
    text = client.get("/metrics").text          # a scrape exports
    assert "# TYPE dllm_tick_phase_ms_total counter" in text
    first = _phase_counter(obs)
    totals = engine.profiler.self_totals()
    for phase, total in totals.items():
        if phase == "decode":
            # A tick from its launch to its fetch, all of it its
            # children's (``span_from``): nothing to count, no series.
            assert total == 0.0 and ("nano", phase) not in first
        elif phase == "idle_wait":              # still growing: idle engine
            assert first[("nano", phase)] <= total + 1e-6
        else:
            assert first[("nano", phase)] == pytest.approx(total, abs=1e-6)
    assert {"prepare", "dispatch", "fetch", "account"} <= {
        k[1] for k in first}
    # Never falls: another scrape, then an engine "rebuilt" from 0.
    client.get("/metrics")
    second = _phase_counter(obs)
    assert all(second[k] >= v for k, v in first.items())

    class _Rebuilt(P.TickProfiler):
        def self_totals(self):
            return {"emit": 0.25}

    real = router._live_profilers
    router._live_profilers = lambda: iter([("nano", _Rebuilt("nano"))])
    try:
        router.export_tick_totals()
        router.export_tick_totals()
    finally:
        router._live_profilers = real
    third = _phase_counter(obs)
    assert third[("nano", "emit")] == pytest.approx(
        second[("nano", "emit")] + 0.25)


def test_first_delta_hold_observed_once_per_streamed_request(timeline_app):
    client, _router, obs = timeline_app
    hist = obs.m.first_delta_hold_ms.labels("heuristic")
    before = hist.count
    for i in range(2):
        resp = client.post("/chat/stream", json={
            "message": f"stream timeline {i}", "strategy": "heuristic",
            "session_id": f"st{i}"})
        assert resp.status_code == 200
        assert '"done"' in resp.text            # consumed to its end
    assert hist.count == before + 2
    assert hist.sum >= 0.0
    # A non-streamed request has no edge hold-back to measure.
    client.post("/chat", json={"message": "not streamed",
                               "strategy": "heuristic",
                               "session_id": "ns"})
    assert hist.count == before + 2
    # The engine's split of TTFT is observed beside the queue wait.
    qw = obs.m.queue_wait_ms.labels("nano").count
    assert obs.m.prefill_wait_ms.labels("nano").count == qw
    assert obs.m.prefill_lane_wait_ms.labels("nano").count == qw
    assert obs.m.prefill_lane_wait_ms.labels("nano").sum == 0.0


def test_lane_wait_zero_unblocked_positive_behind_a_chunked_prefill():
    """Two long prompts, one prefill lane: the second sits at the head
    while ``head_blocked`` holds it."""
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    tier = dataclasses.replace(
        tiny_cluster().nano, max_new_tokens=8, decode_batch=2,
        enable_prefix_cache=False, prefill_chunk_tokens=16)
    eng = ContinuousBatchingEngine(tier, seed=11)
    try:
        traces = [RequestTrace(f"lane-{i}") for i in range(3)]
        reqs = []
        for trace, prompt in zip(traces, (LONG_Q, LONG_Q + " again",
                                          "user: short one")):
            with use_trace(trace):
                reqs.append(eng.submit(prompt))
        for r in reqs:
            assert r.done.wait(timeout=120) and r.error is None
        first, second, short = (t.attrs for t in traces)
        assert first["lane_wait_ms"] == 0.0
        assert second["lane_wait_ms"] > 0.0
        assert second["lane_wait_ms"] <= second["queue_wait_ms"] + 1e-3
        assert short["lane_wait_ms"] == 0.0     # never needed the lane
        assert first["prefill_wait_ms"] > 0.0
    finally:
        eng.stop()


# -- D. /debug/trace -----------------------------------------------------------

def test_debug_trace_window_and_clock_origin(timeline_app):
    client, router, _obs = timeline_app
    engine = router.tiers["nano"].server_manager.engine()
    full = client.get("/debug/trace").get_json()
    meta = full["metadata"]
    wall_minus_perf = time.time() - time.perf_counter()
    assert meta["ts_origin_unix_s"] - meta["ts_origin_perf_counter_s"] \
        == pytest.approx(wall_minus_perf, abs=0.05)
    # The origin maps a slice back to perf_counter: the engine's own
    # record stamps.
    ticks = [e for e in full["traceEvents"]
             if e["ph"] == "X" and e["name"] == "tick"]
    recs = {r["seq"]: r for r in engine.profiler.records()}
    assert len(ticks) >= 3
    for e in ticks:
        t_perf = meta["ts_origin_perf_counter_s"] + e["ts"] / 1e6
        assert t_perf == pytest.approx(recs[e["args"]["seq"]]["t0"],
                                       abs=2e-6)
    # A window around the middle tick keeps it and drops the ends.
    mid = ticks[len(ticks) // 2]
    lo = meta["ts_origin_unix_s"] + mid["ts"] / 1e6
    hi = lo + mid["dur"] / 1e6
    cut = client.get(f"/debug/trace?since={lo!r}&until={hi!r}").get_json()
    kept = [e["args"]["seq"] for e in cut["traceEvents"]
            if e["ph"] == "X" and e["name"] == "tick"]
    assert mid["args"]["seq"] in kept
    assert ticks[0]["args"]["seq"] not in kept
    assert ticks[-1]["args"]["seq"] not in kept
    assert len(kept) < len(ticks)
    # since alone, until alone.
    late = client.get(f"/debug/trace?since={hi + 1e-4!r}").get_json()
    early = client.get(f"/debug/trace?until={lo - 1e-4!r}").get_json()
    n = lambda doc: sum(1 for e in doc["traceEvents"]        # noqa: E731
                        if e["ph"] == "X" and e["name"] == "tick")
    assert n(late) + n(early) + 1 == len(ticks)
    assert client.get("/debug/trace?since=yesterday").status_code == 400


# -- overhead ------------------------------------------------------------------

def test_full_stamp_set_with_annotations_within_one_percent(timeline_engine):
    """The pin of tests/test_profiler.py, with everything a decode tick
    stamps since ISSUE 26 and the real ``TraceAnnotation`` in (no
    capture running: one inactive TraceMe per stamp)."""
    p50 = timeline_engine.tick_stats()["p50_ms"]
    assert p50 is not None
    prof = P.TickProfiler("bench")
    assert prof._annotation is jax.profiler.TraceAnnotation
    n = 400
    t0 = time.perf_counter()
    for _ in range(n):
        with prof.phase("admit"):
            pass
        with prof.phase("prepare"):
            pass
        with prof.phase("prepare"):
            with prof.phase("table_upload"):
                pass
        with prof.phase("decode"):
            with prof.phase("dispatch"):
                pass
            with prof.phase("fetch"):
                pass
        with prof.phase("account"):
            pass
        with prof.phase("emit"):
            pass
        prof.commit(4)
    per_tick_ms = (time.perf_counter() - t0) * 1000.0 / n
    assert per_tick_ms < max(0.01 * p50, 0.05), (
        f"profiler costs {per_tick_ms:.4f} ms/tick vs tick p50 {p50} ms")
