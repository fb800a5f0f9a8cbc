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
        self.cpu = 7.0

    def perf_counter(self) -> float:
        return self.now

    def thread_time(self) -> float:
        return self.cpu

    def time(self) -> float:
        return self.now + 1.7e9

    def sleep(self, seconds: float) -> None:
        """Off the CPU: the wall clock alone moves."""
        self.now += seconds

    def work(self, seconds: float) -> None:
        """On the CPU: both clocks move."""
        self.now += seconds
        self.cpu += seconds


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
             for name, _rel, dur, self_ms, _cpu in rec["spans"]}
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
    # ``decode`` is a tick from its launch to its fetch: it ends in its
    # own pass and, dispatched ahead of the fetch before it (PR 52),
    # begins in the pass before.
    for ph in phases:
        ends_in = [t for t in ticks if t["ts"] - 1 <= ph["ts"] + ph["dur"]
                   <= t["ts"] + t["dur"] + 1]
        assert ends_in, ph
        begins = ticks[0] if ph["name"] == "decode" else ends_in[0]
        assert begins["ts"] - 1 <= ph["ts"], ph
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


# -- CPU beside wall (ISSUE 41) ----------------------------------------------

def test_self_cpu_partitions_the_records_cpu(monkeypatch):
    """Self CPU is to a record's CPU what self-time is to its wall: a
    parent's excludes its children's, and the parts add up."""
    clock = _FakeClock()
    monkeypatch.setattr(P, "time", clock)
    prof = P.TickProfiler("t", capacity=16)
    with prof.phase("admit"):
        clock.work(0.002)
        with prof.phase("prefill"):
            clock.work(0.001)
            clock.sleep(0.004)              # waits for the device
    with prof.phase("emit"):
        clock.work(0.0005)
        clock.sleep(0.0015)                 # stood there, did not run
    prof.commit(slots=1)
    (rec,) = prof.records()
    spans = {name: (dur, self_ms, cpu_ms)
             for name, _rel, dur, self_ms, cpu_ms in rec["spans"]}
    assert spans["admit"] == pytest.approx((7.0, 2.0, 2.0))
    assert spans["prefill"] == pytest.approx((5.0, 5.0, 1.0))
    assert spans["emit"] == pytest.approx((2.0, 2.0, 0.5))
    assert rec["cpu_ms"] == pytest.approx(3.5)
    assert sum(c for _, _, c in spans.values()) == pytest.approx(
        rec["cpu_ms"])
    assert sum(s for _, s, _ in spans.values()) == pytest.approx(
        rec["dur_ms"])
    # Lifetime totals, the counters' source, and what /stats shows.
    assert prof.cpu_totals() == pytest.approx(
        {"admit": 2.0, "prefill": 1.0, "emit": 0.5})
    assert prof.self_totals()["emit"] - prof.cpu_totals()["emit"] == \
        pytest.approx(1.5)
    assert prof.phase_stats()["totals"]["emit"] == {
        "n": 1, "total_ms": 2.0, "cpu_ms": 0.5}
    # The idle wait keeps both clocks too, and leaves no record.
    with prof.idle_wait():
        clock.work(0.0001)
        clock.sleep(0.05)
    assert prof.cpu_totals()["idle_wait"] == pytest.approx(0.1)
    assert prof.self_totals()["idle_wait"] == pytest.approx(50.1)
    # /debug/trace: cpu_ms beside self_ms on every phase slice.
    doc = P.chrome_trace({"t": prof.snapshot()})
    emit = next(e for e in doc["traceEvents"] if e["name"] == "emit")
    assert emit["args"] == {"self_ms": 2.0, "cpu_ms": 0.5}
    tick = next(e for e in doc["traceEvents"] if e["name"] == "tick")
    assert tick["args"]["cpu_ms"] == 3.5


class _SlowCpuClock(_FakeClock):
    """A thread CPU clock that costs ``cost`` seconds of wall a reading
    (the v5e hosts' sandboxed kernel) and counts its readings."""

    def __init__(self, cost):
        super().__init__()
        self.cost = cost
        self.readings = 0

    def thread_time(self) -> float:
        self.readings += 1
        self.now += self.cost
        return self.cpu


@pytest.mark.parametrize("cost, stride", [(0.0, 1), (0.4e-6, 1),
                                          (5.7e-6, P.CPU_PASS_EVERY)])
def test_a_dear_cpu_clock_is_read_one_pass_in_five(monkeypatch, cost, stride):
    """Where a reading of the thread's CPU clock is a slow system call
    the profiler takes it on one pass in CPU_PASS_EVERY; where it is
    cheap, on every pass."""
    monkeypatch.setattr(P, "time", _SlowCpuClock(cost))
    assert P._cpu_pass_stride() == stride
    assert P.TickProfiler("t", capacity=16).cpu_every == stride


def test_a_pass_that_reads_the_cpu_clock_stands_for_its_stride(monkeypatch):
    """With ``cpu_every`` 3, passes 0 and 3 read the CPU clock and
    count threefold in the lifetime totals; passes 1 and 2 make no
    reading and their spans and records carry None."""
    clock = _SlowCpuClock(0.0)
    monkeypatch.setattr(P, "time", clock)
    prof = P.TickProfiler("t", capacity=16, cpu_every=3)
    per_pass = []
    for _ in range(4):
        before = clock.readings
        with prof.phase("emit"):
            clock.work(0.001)
            clock.sleep(0.001)
        with prof.phase("decode"):
            with prof.phase("fetch"):
                clock.work(0.0005)
                clock.sleep(0.02)
        prof.commit(slots=1)
        per_pass.append(clock.readings - before)
    assert per_pass[1:3] == [0, 0] and per_pass[0] == per_pass[3] > 0
    recs = prof.records()
    assert [r["cpu_ms"] for r in recs] == [
        pytest.approx(1.5), None, None, pytest.approx(1.5)]
    for rec, read in zip(recs, (True, False, False, True)):
        cpus = {name: cpu for name, _r, _d, _s, cpu in rec["spans"]}
        assert cpus == (pytest.approx({"emit": 1.0, "fetch": 0.5,
                                       "decode": 0.0}) if read else
                        {"emit": None, "fetch": None, "decode": None})
    # Two passes read, each for three: an estimate of six passes' CPU
    # beside four passes' wall (it meets the wall's count every third).
    assert prof.cpu_totals() == pytest.approx(
        {"emit": 6.0, "fetch": 3.0, "decode": 0.0})
    assert prof.self_totals() == pytest.approx(
        {"emit": 8.0, "fetch": 82.0, "decode": 0.0})
    # /debug/trace: a slice of a pass that was not read has no cpu_ms.
    doc = P.chrome_trace({"t": prof.snapshot()})
    emits = [e["args"] for e in doc["traceEvents"] if e["name"] == "emit"]
    assert emits == [{"self_ms": 2.0, "cpu_ms": 1.0}, {"self_ms": 2.0},
                     {"self_ms": 2.0}, {"self_ms": 2.0, "cpu_ms": 1.0}]
    ticks = [e["args"] for e in doc["traceEvents"] if e["name"] == "tick"]
    assert ["cpu_ms" in a for a in ticks] == [True, False, False, True]


def _spin(seconds):
    """Python that keeps the interpreter busy (the device of
    benchmark/tests/test_hoststalls.py)."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        sum(range(200_000))


def test_a_phase_beside_a_spinning_thread_is_off_the_cpu(monkeypatch):
    """Wall minus CPU is the time the stamping thread stood in a phase
    without running: most of it when another thread holds the
    interpreter for a long switch interval, none when alone.  The
    arithmetic, with clocks the test moves (ROADMAP D14: timed live, six
    test workers on the box decided the result); the live threads are
    ``..._live`` below, under the ``slow`` mark."""
    clock = _FakeClock()
    monkeypatch.setattr(P, "time", clock)
    beside = P.TickProfiler("t", capacity=16, cpu_every=1)
    with beside.phase("emit"):
        for _ in range(2):
            clock.work(0.0001)      # asks for the interpreter back
            clock.sleep(0.005)      # its own sleep
            clock.sleep(0.1)        # the spinner's switch interval
    beside.commit(1)
    wall, cpu = beside.self_totals()["emit"], beside.cpu_totals()["emit"]
    assert wall == pytest.approx(210.2) and cpu == pytest.approx(0.2)
    assert wall - cpu >= 0.8 * wall
    alone = P.TickProfiler("t", capacity=16, cpu_every=1)
    with alone.phase("emit"):
        clock.work(0.005)
    alone.commit(1)
    assert alone.self_totals()["emit"] == pytest.approx(5.0)
    assert alone.cpu_totals()["emit"] == pytest.approx(5.0)
    (rec,) = alone.records()
    assert rec["cpu_ms"] == pytest.approx(5.0)


@pytest.mark.slow
def test_a_phase_beside_a_spinning_thread_is_off_the_cpu_live():
    """The same with a real spinner and the real clocks: a timing, so
    not tier 1's (a loaded box can lose every try)."""
    import sys
    import threading
    old = sys.getswitchinterval()
    seen = []
    for _ in range(3):          # a loaded box can keep the spinner off a core
        prof = P.TickProfiler("t", capacity=16)
        spinner = threading.Thread(target=_spin, args=(0.6,), daemon=True)
        sys.setswitchinterval(0.1)
        try:
            spinner.start()
            time.sleep(0.02)                # the spinner has the interpreter
            with prof.phase("emit"):
                for _ in range(2):
                    time.sleep(0.005)       # gives it up, asks for it back
        finally:
            sys.setswitchinterval(old)
        spinner.join()
        prof.commit(1)
        wall, cpu = prof.self_totals()["emit"], prof.cpu_totals()["emit"]
        seen.append((wall, cpu))
        # Ten of those milliseconds are the two sleeps; the rest is the
        # wait for the interpreter.
        if wall >= 100.0 and wall - cpu >= 0.8 * wall:
            break
    else:
        raise AssertionError(seen)
    # Alone: the best of a dozen short tries, since six test workers
    # share the box and any one try can lose its core.
    shares = []
    for _ in range(12):
        alone = P.TickProfiler("t", capacity=16)
        with alone.phase("emit"):
            _spin(0.005)
        alone.commit(1)
        wall, cpu = alone.self_totals()["emit"], alone.cpu_totals()["emit"]
        shares.append((wall - cpu) / wall)
        if shares[-1] < 0.1:
            break
    assert min(shares) < 0.25, shares


def test_runqueue_wait_rides_on_the_record_where_schedstat_reads(
        monkeypatch, tmp_path):
    import os
    prof = P.TickProfiler("t", capacity=16)
    for _ in range(3):
        with prof.phase("emit"):
            pass
        prof.commit(1)
    recs = prof.records()
    if os.path.exists(P.SCHEDSTAT_PATH):
        assert recs[0]["runq_ms"] is None       # a thread's first reading
        assert all(r["runq_ms"] >= 0.0 for r in recs[1:])
        assert prof.runqueue_wait_ms() == pytest.approx(
            sum(r["runq_ms"] for r in recs[1:]))
    # A file with known numbers: growth since the commit before.
    stat = tmp_path / "schedstat"
    monkeypatch.setattr(P, "SCHEDSTAT_PATH", str(stat))
    prof = P.TickProfiler("t", capacity=16)
    for wait_ns in (5_000_000, 5_250_000, 7_250_000):
        stat.write_text(f"123456 {wait_ns} 9\n")
        with prof.phase("emit"):
            pass
        prof.commit(1)
    assert [r["runq_ms"] for r in prof.records()] == [None, 0.25, 2.0]
    assert prof.runqueue_wait_ms() == 2.25
    # Nothing to read: the figure is absent and nothing else changes.
    monkeypatch.setattr(P, "SCHEDSTAT_PATH", str(tmp_path / "absent"))
    prof = P.TickProfiler("t", capacity=16)
    with prof.phase("emit"):
        pass
    prof.commit(1)
    (rec,) = prof.records()
    assert rec["runq_ms"] is None and prof.runqueue_wait_ms() is None
    doc = P.chrome_trace({"t": prof.snapshot()})
    tick = next(e for e in doc["traceEvents"] if e["name"] == "tick")
    assert "runq_ms" not in tick["args"] and "cpu_ms" in tick["args"]


# -- edge lanes (ISSUE 41) ---------------------------------------------------

def test_null_profilers_edge_lane_is_the_shared_singleton():
    import sys
    null = P.NULL_PROFILER
    tr = RequestTrace(strategy="t")
    assert null.edge_lane(tr) is null.edge_lane(None) is P._NULL_LANE
    lane = null.edge_lane(tr)
    lane.open()
    before = sys.getallocatedblocks()
    for taken in range(1000):
        null.edge_lane(tr)
        assert lane.sleep(taken) is None and lane.wake() is None
    lane.close(1000)
    assert sys.getallocatedblocks() - before < 16
    assert null.snapshot() == {"records": [], "events": []}


def test_one_wake_is_one_slice_and_lanes_never_overlap(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(P, "time", clock)
    prof = P.TickProfiler("t", capacity=16)
    tr = RequestTrace(strategy="t")
    # Another tier wrote two tokens before this stream (a failover).
    tr.token_times.extend([99.0, 99.5])
    lane = prof.edge_lane(tr)
    lane.open()                             # awake since it started ...
    lane.sleep(0)                           # ... until its first wait
    lane.sleep(0)                           # nothing open: no slice
    tr.token_times.extend([clock.now] * 4)  # the emit stamps 4 tokens ...
    clock.sleep(0.003)                      # ... the consumer runs 3 ms on
    lane.wake()
    clock.work(0.0002)                      # takes all four
    clock.sleep(0.0001)
    lane.sleep(4)
    clock.sleep(1.2)                        # past EDGE_CPU_EVERY_S
    tr.token_times.append(clock.now)
    clock.sleep(0.001)
    lane.wake()
    clock.work(0.0001)
    lane.sleep(5)
    clock.sleep(0.002)
    lane.wake()                             # woken by the end alone
    lane.close(5)
    lane.close(5)                           # closing twice folds once
    slices = prof.edge_slices()
    assert [(s[0], s[1], s[5]) for s in slices] == [
        (0, tr.request_id, 0), (0, tr.request_id, 4), (0, tr.request_id, 1),
        (0, tr.request_id, 0)]
    assert [s[6] for s in slices] == pytest.approx([None, 3.0, 1.0, None])
    # The CPU clock is read at most every EDGE_CPU_EVERY_S a stream and
    # at its end: the slice that reads it carries the CPU since the
    # reading before, the slices between carry none.
    assert [s[4] for s in slices] == [None, None, pytest.approx(0.3),
                                      pytest.approx(0.0)]
    assert slices[1][3] - slices[1][2] == pytest.approx(0.0003)
    totals = prof.edge_totals()
    assert totals["wakeups"] == 4 and totals["tokens"] == 5
    assert totals["wall_ms"] == pytest.approx(0.4)
    assert totals["cpu_ms"] == pytest.approx(0.3)
    assert totals["lag_n"] == 2 and totals["lag_ms"] == pytest.approx(4.0)
    assert sum(totals["lag_counts"]) == 2
    # A lane number is held by one live stream at a time: the lowest
    # free one, so concurrent streams never share a lane and the lanes
    # stay as few as the streams.
    a, b, c = (prof.edge_lane(None) for _ in range(3))
    for x in (a, b, c):
        x.open()
    assert (a.lane, b.lane, c.lane) == (0, 1, 2)
    b.close(0)
    d = prof.edge_lane(None)
    d.open()
    assert d.lane == 1
    for x in (a, c, d):
        x.sleep(0)
        x.wake()
        clock.work(0.001)
        x.sleep(1)
    by_lane = {}
    for s in prof.edge_slices():
        by_lane.setdefault(s[0], []).append((s[2], s[3]))
    for spans in by_lane.values():
        spans.sort()
        assert all(a_end <= b_start for (_, a_end), (b_start, _)
                   in zip(spans, spans[1:])), spans
    # Live lanes count in the totals before their streams end.
    assert prof.edge_totals()["tokens"] == 8


def test_edge_ring_stays_bounded(monkeypatch):
    # 120 s at 30 passes a second and 16 streams woken a pass.
    assert P.TickProfiler("t")._edge_ring.maxlen >= 120 * 30 * 16
    monkeypatch.setattr(P, "EDGE_CAPACITY", 32)
    prof = P.TickProfiler("t", capacity=16)
    lane = prof.edge_lane(None)
    lane.open()
    for taken in range(100):
        lane.sleep(taken)
        lane.wake()
    lane.close(100)
    assert len(prof.edge_slices()) == 32
    totals = prof.edge_totals()                     # totals outlive it
    assert totals["wakeups"] == 101 and totals["tokens"] == 100


def test_edge_slices_rest_untracked_by_the_collector():
    """A slice at rest is a ``bytes`` record: nothing the garbage
    collector tracks, so a stream's wake-ups bring no collection
    forward; what is None (no token taken, the CPU clock not read)
    comes back None."""
    import gc
    prof = P.TickProfiler("t", capacity=16)
    lane = prof.edge_lane(None)
    lane.open()
    lane.sleep(0)                       # took nothing: no lag, no CPU
    lane.wake()
    lane.close(3)                       # the end reads the CPU clock
    assert len(prof._edge_ring) == 2
    assert not any(gc.is_tracked(raw) for raw in prof._edge_ring)
    first, last = prof.edge_slices()
    assert first[4] is None and first[5] == 0 and first[6] is None
    assert last[4] is not None and last[5] == 3
    assert first[:2] == (0, 0) and first[2] <= first[3] <= last[2] <= last[3]


def test_edge_lanes_leave_the_tier_thread_as_it_was():
    prof = P.TickProfiler("nano", capacity=16)
    other = P.TickProfiler("orin", capacity=16)
    for p in (prof, other):
        for _ in range(3):
            with p.phase("decode"):
                with p.phase("fetch"):
                    pass
            lane = p.edge_lane(RequestTrace(strategy="t"))
            lane.open()
            lane.sleep(0)
            with p.phase("emit"):
                lane.wake()
            lane.close(4)
            p.commit(2)
        p.event("compile", stage="decode")
    snaps = {"nano": prof.snapshot(), "orin": other.snapshot()}
    bare = {k: dict(v, edge=[]) for k, v in snaps.items()}
    with_lanes = P.chrome_trace(snaps)
    without = P.chrome_trace(bare)
    n = len(without["traceEvents"])
    # Byte for byte: the tier threads' events come first and are the
    # same, and so is the origin of the ts axis.
    assert json.dumps(with_lanes["traceEvents"][:n]) == json.dumps(
        without["traceEvents"])
    assert with_lanes["metadata"]["ts_origin_perf_counter_s"] == \
        without["metadata"]["ts_origin_perf_counter_s"]
    rest = with_lanes["traceEvents"][n:]
    names = [e["args"]["name"] for e in rest if e["ph"] == "M"]
    assert names == ["edge:nano:0", "edge:orin:0"]
    tier_tids = {e["tid"] for e in without["traceEvents"]}
    assert not tier_tids & {e["tid"] for e in rest}
    awake = [e for e in rest if e["ph"] == "X"]
    assert len(awake) == 12 and all(
        e["name"] == "edge_awake" and "request_id" in e["args"]
        for e in awake)
    assert sorted(e["args"]["tokens"] for e in awake) == [0] * 6 + [4] * 6
    # The same cut: a window that ends before the stamps keeps nothing.
    cut = P.chrome_trace(snaps, until=time.time() - 3600.0)
    assert cut["traceEvents"] == [
        e for e in with_lanes["traceEvents"]
        if e["ph"] == "M" and e["args"]["name"].startswith("tier:")]


def test_edge_stamps_cost_little_a_wake():
    """The consumer's budget: no lock and two clock pairs a wake.  A
    loose pin (this box's clocks cost a microsecond a call): a lock or
    an allocation storm on the path would blow it."""
    prof = P.TickProfiler("bench", capacity=16)
    tr = RequestTrace(strategy="t")
    tr.token_times.extend([time.perf_counter()] * 4000)
    lane = prof.edge_lane(tr)
    lane.open()
    n = 1000
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        lane.sleep(4 * (i - 1))
        lane.wake()
    lane.close(4 * n)
    per_wake_us = (time.perf_counter() - t0) * 1e6 / n
    assert per_wake_us < 40.0, per_wake_us
    assert prof.edge_totals()["tokens"] == 4 * n


@pytest.fixture(scope="module")
def streamed_app():
    from distributed_llm_tpu.serving.app import create_app
    from distributed_llm_tpu.serving.router import Router
    obs = Observability(slow_ms=None)
    router = Router(strategy="heuristic", benchmark_mode=True,
                    cluster=tiny_batched_cluster(), observability=obs)
    yield create_app(router=router).test_client(), router, obs
    router.drain()


def _stream(client, message, session):
    resp = client.post("/chat/stream", json={
        "message": message, "strategy": "heuristic", "session_id": session})
    assert resp.status_code == 200
    done = None
    for frame in resp.text.split("\n\n"):
        if frame.startswith("data: "):
            ev = json.loads(frame[len("data: "):])
            if ev.get("done"):
                done = ev
    assert done is not None
    return done


def test_streamed_requests_add_up_in_the_edge_counters(streamed_app):
    client, router, obs = streamed_app
    t_unix = time.time()
    tokens = sum(_stream(client, f"edge lane question {i}", f"e{i}")["tokens"]
                 for i in range(2))
    assert tokens > 0
    text = client.get("/metrics").text          # a scrape exports

    def value(name, **labels):
        fam = obs.metrics.get(name)
        assert fam is not None, name
        key = tuple(labels[k] for k in fam.label_names)
        return fam.labels(*key)

    assert value("dllm_edge_tokens_total", tier="nano").value == tokens
    wakeups = value("dllm_edge_wakeups_total", tier="nano").value
    assert 4 <= wakeups <= tokens + 4           # a start and an end a stream
    wall = value("dllm_edge_awake_ms_total", tier="nano", clock="wall").value
    cpu = value("dllm_edge_awake_ms_total", tier="nano", clock="cpu").value
    # (A lane's CPU is its thread's between two readings: the slices
    # and the ``get``s' own work around the waits between them.)
    assert 0.0 < cpu <= wall + 0.2 * wakeups
    lag = value("dllm_edge_wake_lag_ms", tier="nano")
    assert 2 <= lag.count <= wakeups and lag.sum > 0.0
    assert "# TYPE dllm_edge_wake_lag_ms histogram" in text
    assert 'dllm_tick_phase_cpu_ms_total{tier="nano",phase="emit"}' in text
    # CPU never exceeds wall by more than the clocks' grain, phase by
    # phase, and the two families carry the same phases.
    prof = router.tiers["nano"].server_manager.engine().profiler
    walls, cpus = prof.self_totals(), prof.cpu_totals()
    assert set(walls) == set(cpus)
    assert all(cpus[p] <= walls[p] * 1.05 + 0.5 for p in walls), (
        walls, cpus)
    # A second scrape exports the growth only.
    client.get("/metrics")
    assert value("dllm_edge_tokens_total", tier="nano").value == tokens
    assert lag.count <= wakeups

    # /debug/trace: the lanes, every slice carrying its request, and its
    # first token stamped inside an emit or an admit slice of the tier.
    # (The pass that emitted the last tokens commits its record after
    # the stream's end has reached the client.)
    deadline = time.time() + 5.0
    while prof._t0 is not None and time.time() < deadline:
        time.sleep(0.01)
    doc = client.get(f"/debug/trace?since={t_unix - 1.0}").get_json()
    events = doc["traceEvents"]
    threads = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    tier_tid = next(t for t, n in threads.items() if n == "tier:nano")
    assert any(n.startswith("edge:nano:") for n in threads.values())
    awake = [e for e in events if e["name"] == "edge_awake"]
    assert sum(e["args"]["tokens"] for e in awake) == tokens
    assert len({e["args"]["request_id"] for e in awake}) == 2
    causes = [(e["ts"], e["ts"] + e["dur"]) for e in events
              if e["tid"] == tier_tid and e["name"] in ("emit", "admit")]
    for e in awake:
        if "wake_lag_ms" not in e["args"]:
            continue
        stamp = e["ts"] - 1000.0 * e["args"]["wake_lag_ms"]
        assert any(a - 1.0 <= stamp <= b + 1.0 for a, b in causes), (
            e, [x for x in events if x["tid"] == tier_tid and x["ph"] == "X"
                and x["ts"] - 1.0 <= stamp <= x["ts"] + x["dur"] + 1.0])


def test_profile_off_streams_without_a_lane(monkeypatch):
    """DLLM_PROFILE=0: the stream's consumer gets the shared null lane
    (nothing is made a stream, a wake or a token) and no edge counter
    appears."""
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    monkeypatch.setenv("DLLM_PROFILE", "0")

    def boom(*a, **kw):
        raise AssertionError("an EdgeLane was made with the profiler off")
    monkeypatch.setattr(P.EdgeLane, "__init__", boom)
    eng = ContinuousBatchingEngine(tiny_batched_cluster().nano, seed=5)
    try:
        assert eng.profiler is P.NULL_PROFILER
        tr = RequestTrace(strategy="t")
        with use_trace(tr):
            handle = eng.generate_stream("hello off path", max_new_tokens=6)
        text = "".join(handle)
        assert handle.result is not None and handle.result.gen_tokens > 0
        assert isinstance(text, str)
        assert eng.profiler.snapshot() == {"records": [], "events": []}
    finally:
        eng.stop()
