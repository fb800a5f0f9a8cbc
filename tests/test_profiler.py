"""Tick forensics (ISSUE 11): the tick-phase profiler, Chrome-trace
export, and per-request device-time / KV cost attribution.

Engine-level tests drive a real tiny batched engine (module-scoped —
one build serves every read-only assertion); the serving-surface test
goes through create_app so /debug/trace, /metrics and /stats are
exercised exactly as a scraper sees them."""

import dataclasses
import json
import time

import pytest

from distributed_llm_tpu.config import tiny_batched_cluster
from distributed_llm_tpu.obs import Observability
from distributed_llm_tpu.obs import profiler as P
from distributed_llm_tpu.obs.spans import RequestTrace, use_trace


# -- TickProfiler unit mechanics ---------------------------------------------

class _FakeClock:
    """Stands in for the profiler module's ``time``: the test advances
    it by hand, so the self-time arithmetic is checked exactly and a
    descheduled worker (6 xdist workers share the box) cannot stretch a
    span."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds


def test_phase_nesting_self_time_and_ring_bound(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(P, "time", clock)
    prof = P.TickProfiler("t", capacity=16)
    with prof.phase("admit"):
        clock.sleep(0.002)
        with prof.phase("prefill"):
            clock.sleep(0.005)
    prof.commit(slots=2)
    (rec,) = prof.records()
    assert rec["slots"] == 2 and rec["seq"] == 1
    spans = {name: (dur, self_ms)
             for name, _rel, dur, self_ms in rec["spans"]}
    # The child's full duration is excluded from the parent's SELF time
    # (self-times partition the tick wall; durations nest).
    assert spans["admit"][0] == pytest.approx(7.0)
    assert spans["admit"][1] == pytest.approx(2.0)
    assert spans["prefill"][0] == pytest.approx(5.0)
    assert spans["prefill"][0] == pytest.approx(spans["prefill"][1])
    total_self = sum(s for _, s in spans.values())
    assert total_self <= rec["dur_ms"] * 1.001
    st = prof.phase_stats()
    assert st["coverage"] is not None and st["coverage"] > 0.9
    assert st["phases"]["prefill"]["n"] == 1
    # Lifetime totals survive ring eviction.
    for _ in range(40):
        with prof.phase("decode"):
            pass
        prof.commit(1)
    assert len(prof.records()) == 16            # ring bound holds
    assert prof.phase_stats()["totals"]["decode"]["n"] == 40
    # Idle commits (nothing stamped) leave no record.
    n = len(prof.records())
    prof.commit(0)
    assert len(prof.records()) == n


def test_null_profiler_allocates_nothing_and_records_nothing(monkeypatch):
    monkeypatch.setenv("DLLM_PROFILE", "0")
    prof = P.make_profiler("nano")
    assert prof is P.NULL_PROFILER              # shared singleton
    assert prof.enabled is False
    # The off path allocates nothing per stamp: every phase() call
    # returns the one shared null context manager.
    assert prof.phase("decode") is prof.phase("emit")
    with prof.phase("decode"):
        prof.event("compile", stage="decode")
    prof.commit(4)
    assert prof.records() == [] and prof.events() == []
    assert prof.phase_stats()["ticks"] == 0
    assert prof.summary() == {"enabled": False}
    monkeypatch.setenv("DLLM_PROFILE", "1")
    assert P.make_profiler("nano") is not P.NULL_PROFILER


def test_chrome_trace_export_of_empty_snapshot():
    doc = P.chrome_trace({})
    assert doc["traceEvents"] == []
    json.dumps(doc)                             # serializable


# -- engine integration ------------------------------------------------------

@pytest.fixture(scope="module")
def profiled_engine():
    """One tiny batched engine that served traced requests: yields
    (engine, traces).  Every test against it is read-only."""
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    tier = tiny_batched_cluster().nano
    eng = ContinuousBatchingEngine(tier, seed=3)
    traces = []
    try:
        reqs = []
        for i in range(4):
            tr = RequestTrace(strategy="t")
            traces.append(tr)
            with use_trace(tr):
                reqs.append(eng.submit(f"profiled question {i}",
                                       max_new_tokens=8))
        for r in reqs:
            r.done.wait(timeout=120)
            assert r.error is None, r.error
        yield eng, traces
    finally:
        eng.stop()


def test_engine_phase_breakdown_covers_tick_wall(profiled_engine):
    eng, _ = profiled_engine
    st = eng.profiler.phase_stats()
    assert st["ticks"] >= 1
    assert {"admit", "prepare", "decode", "dispatch", "fetch", "account",
            "emit"} <= set(st["phases"])
    for entry in st["phases"].values():
        assert entry["p50_ms"] <= entry["p95_ms"] or entry["n"] == 1
    # Acceptance: stamped phases explain >= 95% of tick wall time.
    assert st["coverage"] >= 0.95, st
    # Compile events were stitched onto the timeline.
    assert any(name == "compile" for name, _t, _a in eng.profiler.events())


def test_attribution_conservation_and_kv_ticks(profiled_engine):
    """The even per-tick split must re-add to what the decode phases
    actually cost (5% bar), and KV residency bills blocks x ticks."""
    eng, traces = profiled_engine
    attributed = sum(tr.device_time_ms for tr in traces)
    decode_total = eng.profiler.total_ms("decode")
    assert decode_total > 0
    assert attributed == pytest.approx(decode_total, rel=0.05)
    assert all(tr.device_time_ms > 0 for tr in traces)
    assert all(tr.kv_block_ticks > 0 for tr in traces)
    # Serialized traces (what the flight recorder stores) carry both.
    d = traces[0].to_dict()
    assert d["device_time_ms"] > 0 and d["kv_block_ticks"] > 0


def test_chrome_trace_schema_roundtrip(profiled_engine):
    """GET /debug/trace's contract: valid Chrome-trace JSON whose tick
    slices are timestamp-monotonic per tier and whose phase slices nest
    inside their tick."""
    eng, _ = profiled_engine
    doc = json.loads(json.dumps(P.chrome_trace(
        {"nano": eng.profiler.snapshot()})))
    events = doc["traceEvents"]
    assert events and doc["displayTimeUnit"] == "ms"
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] != "M":
            assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
    ticks = [e for e in events
             if e["ph"] == "X" and e["name"] == "tick"]
    assert ticks
    seqs = [t["args"]["seq"] for t in ticks]
    tss = [t["ts"] for t in ticks]
    assert seqs == sorted(seqs) and tss == sorted(tss)  # monotonic
    # Phase slices sit inside some tick slice's [ts, ts+dur] window.
    phases = [e for e in events
              if e["ph"] == "X" and e["name"] != "tick"]
    assert phases
    for ph in phases:
        assert any(t["ts"] - 1 <= ph["ts"]
                   and ph["ts"] + ph["dur"] <= t["ts"] + t["dur"] + 1
                   for t in ticks), ph
    # Instant events (compile at minimum) are on the same timeline.
    assert any(e["ph"] == "i" for e in events)


def test_profiler_overhead_within_one_percent_of_tick(profiled_engine):
    """Acceptance: profiler ON adds <= 1% to tick p50 on the tiny CPU
    config.  Measured as the profiler's own per-tick cost (the full
    stamp set a decode tick pays: admit gate check + 4 phases + ring
    commit) against the engine's measured tick p50 — the direct A/B
    (two engines, compare p50s) drowns in this box's run-to-run noise,
    while the stamp cost itself is deterministic."""
    eng, _ = profiled_engine
    p50 = eng.tick_stats()["p50_ms"]
    assert p50 is not None
    prof = P.TickProfiler("bench", capacity=512)
    n = 400
    t0 = time.perf_counter()
    for _ in range(n):
        with prof.phase("admit"):
            pass
        with prof.phase("table_upload"):
            pass
        with prof.phase("decode"):
            pass
        with prof.phase("emit"):
            pass
        prof.commit(4)
    per_tick_ms = (time.perf_counter() - t0) * 1000.0 / n
    assert per_tick_ms < max(0.01 * p50, 0.05), (
        f"profiler costs {per_tick_ms:.4f} ms/tick vs tick p50 {p50} ms")


def test_engine_off_path_charges_nothing(monkeypatch):
    """DLLM_PROFILE=0: the engine gets the shared null profiler, no
    records accrue, and traces stay unbilled."""
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    monkeypatch.setenv("DLLM_PROFILE", "0")
    tier = tiny_batched_cluster().nano
    eng = ContinuousBatchingEngine(tier, seed=5)
    try:
        assert eng.profiler is P.NULL_PROFILER
        tr = RequestTrace(strategy="t")
        with use_trace(tr):
            req = eng.submit("hello off path", max_new_tokens=4)
        req.done.wait(timeout=120)
        assert req.error is None
        assert eng.profiler.records() == []
        assert tr.device_time_ms == 0.0 and tr.kv_block_ticks == 0.0
        assert "device_time_ms" not in tr.to_dict()
    finally:
        eng.stop()


# -- serving surfaces --------------------------------------------------------

@pytest.fixture(scope="module")
def profiled_app():
    from distributed_llm_tpu.serving.app import create_app
    from distributed_llm_tpu.serving.router import Router
    obs = Observability(slow_ms=0.0)            # record every request
    cluster = dataclasses.replace(tiny_batched_cluster())
    router = Router(strategy="heuristic", benchmark_mode=True,
                    cluster=cluster, observability=obs)
    app = create_app(router=router)
    client = app.test_client()
    for i in range(3):
        resp = client.post("/chat", json={"message": f"hi question {i}",
                                          "strategy": "heuristic",
                                          "session_id": f"sess{i % 2}"})
        assert resp.status_code == 200
    yield client, router, obs
    for tier in router.tiers.values():
        tier.server_manager.stop_server()


def test_debug_trace_endpoint_serves_chrome_json(profiled_app):
    client, _router, _obs = profiled_app
    doc = client.get("/debug/trace").get_json()
    events = doc["traceEvents"]
    assert any(e["name"] == "decode" and e["ph"] == "X" for e in events)
    assert any(e["ph"] == "M" and e["args"]["name"].startswith("tier:")
               for e in events)


def test_cost_attribution_aggregates_per_tier_strategy_session(
        profiled_app):
    client, router, obs = profiled_app
    # /metrics: the (tier, strategy, session) families exist and carry
    # the charged totals.
    text = client.get("/metrics").text
    assert "# TYPE dllm_device_time_ms_total counter" in text
    assert 'session="sess0"' in text and 'session="sess1"' in text
    assert "# TYPE dllm_kv_block_ticks_total counter" in text
    fam = obs.metrics.get("dllm_device_time_ms_total")
    assert sum(c.value for c in fam.children().values()) > 0
    # /stats: the bounded ledger, sorted most-expensive-first.
    stats = client.get("/stats").get_json()
    rows = stats["cost"]
    assert rows and {"tier", "strategy", "session", "device_time_ms",
                     "kv_block_ticks", "requests"} <= set(rows[0])
    costs = [r["device_time_ms"] for r in rows]
    assert costs == sorted(costs, reverse=True)
    assert {r["session"] for r in rows} >= {"sess0", "sess1"}
    # health() (embedded in /stats tiers) carries the profiler sideband.
    served = [t for t in stats["tiers"].values()
              if isinstance(t, dict) and t.get("profile")]
    assert served and served[0]["profile"]["enabled"] is True
    # Flight-recorder entries (slow_ms=0 records all) bill per request.
    entry = obs.recorder.snapshot()[0]
    assert entry["trace"]["device_time_ms"] > 0
    assert entry["trace"]["kv_block_ticks"] > 0


def test_cost_ledger_is_bounded():
    from distributed_llm_tpu.serving.router import Router
    r = Router.__new__(Router)                  # ledger methods only
    import threading
    r._cost_lock = threading.Lock()
    r._cost_ledger = {}
    r._cost_ledger_cap = 8
    for i in range(50):
        r._note_cost("nano", "perf", f"s{i}", "default", 1.0, 2.0)
    assert len(r._cost_ledger) == 8
    rows = r.cost_snapshot()
    assert len(rows) == 8
    assert {row["session"] for row in rows} == {f"s{i}"
                                                for i in range(42, 50)}


def test_session_metric_label_is_bounded():
    """session_id is client-controlled: the metric label space must not
    grow without bound — past the cap new sessions aggregate under
    '~overflow', and oversized ids truncate."""
    from distributed_llm_tpu.serving.router import Router
    import threading
    r = Router.__new__(Router)
    r._cost_lock = threading.Lock()
    r._session_labels = set()
    r._session_label_cap = 4
    assert r._session_label(None) == "-"
    assert r._session_label("") == "-"
    labels = {r._session_label(f"s{i}") for i in range(10)}
    assert labels == {"s0", "s1", "s2", "s3", "~overflow"}
    assert r._session_label("s2") == "s2"       # known keeps its label
    assert len(r._session_label("x" * 500)) <= 9  # truncated/overflow


def test_sampler_exports_tick_phase_gauges():
    from distributed_llm_tpu.obs.sampler import SystemStateSampler
    obs = Observability(slow_ms=None)
    s = SystemStateSampler(
        lambda: {"nano": {"queue_depth": 1,
                          "profile_coverage": 0.97,
                          "tick_phases": {"decode": 8.5, "emit": 0.1,
                                          "skipped": None}}},
        metrics=obs.m, period_s=0.02, capacity=8)
    s.sample_once()
    assert obs.metrics.get("dllm_tick_phase_p50_ms").labels(
        "nano", "decode").value == 8.5
    assert obs.metrics.get("dllm_tick_phase_p50_ms").labels(
        "nano", "emit").value == pytest.approx(0.1)
    assert obs.metrics.get("dllm_profile_coverage").labels(
        "nano").value == pytest.approx(0.97)
