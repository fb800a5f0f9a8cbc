"""Serving stack: tiers, lifecycle, Router pipeline, Flask contracts.

Reference parity targets: src/router.py, src/app.py, src/devices/*_api.py,
src/models/{nano,orin}.py, src/models/server_manager.py."""

import json

import pytest

from distributed_llm_tpu.config import PRODUCTION_CFG, tiny_cluster
from distributed_llm_tpu.serving.app import create_app
from distributed_llm_tpu.serving.router import Router
from distributed_llm_tpu.serving.tiers import build_tiers
from distributed_llm_tpu.serving.tpu_api import create_tier_app
from distributed_llm_tpu.utils.faults import FaultInjector


@pytest.fixture(scope="module")
def cluster():
    return tiny_cluster()


def make_router(cluster, **kw):
    kw.setdefault("cluster", cluster)
    return Router(**kw)


# -- tiers & lifecycle ------------------------------------------------------

def test_tier_lazy_start_and_process(cluster):
    tiers = build_tiers(cluster, warmup_on_start=False)
    nano = tiers["nano"]
    assert not nano.server_manager.is_server_running()
    out = nano.process([{"role": "user", "content": "hi"}])
    assert "response" in out
    assert nano.server_manager.is_server_running()
    assert nano.last_result is not None and nano.last_result.ttft_ms > 0


def test_manager_lifecycle_and_health(cluster):
    tiers = build_tiers(cluster, warmup_on_start=False)
    mgr = tiers["orin"].server_manager
    assert mgr.health()["ok"] is False
    mgr.start_server()
    mgr.start_server()          # idempotent
    h = mgr.health()
    assert h["ok"] is True and h["tier"] == "orin" and h["uptime_s"] >= 0
    mgr.stop_server()
    assert not mgr.is_server_running()


def test_fault_injection_shapes(cluster):
    fi = FaultInjector()
    tiers = build_tiers(cluster, fault_injector=fi, warmup_on_start=False)
    fi.timeout_next("nano")
    out = tiers["nano"].process("hi")
    assert "error" in out and "timed out on Nano" in out["error"]
    out2 = tiers["nano"].process("hi")     # one-shot: next call succeeds
    assert "response" in out2
    fi.set_down("nano")
    assert "error" in tiers["nano"].process("hi")
    fi.restore("nano")
    assert "response" in tiers["nano"].process("hi")


# -- Router pipeline --------------------------------------------------------

@pytest.fixture(scope="module")
def bench_router(cluster):
    return make_router(cluster, strategy="heuristic", benchmark_mode=True)


def test_route_query_contract(bench_router):
    resp, tokens, device = bench_router.route_query(
        [{"role": "user", "content": "What is the capital of France"}])
    assert device == "nano"                      # simple pattern
    for key in ("response", "raw", "cache_hit", "routing_overhead_ms",
                "routing_method", "routing_confidence", "routing_reasoning",
                "ok"):
        assert key in resp
    assert resp["ok"] is True and resp["cache_hit"] is False
    assert resp["routing_method"] == "heuristic"
    assert tokens >= 1


def test_router_multi_turn_context(bench_router):
    hist = [
        {"role": "user", "content": "hello"},
        {"role": "assistant", "content": "hi there"},
        {"role": "user", "content": "What is the capital of France"},
    ]
    query, context, ctx_hash = bench_router._history_to_query_and_context(hist)
    assert query == "What is the capital of France"
    assert context == "user: hello\nassistant: hi there"
    assert len(ctx_hash) == 16
    # hash covers only the last-k turns
    q2, c2, h2 = bench_router._history_to_query_and_context(hist[:1] * 9 + hist)
    assert h2 != ctx_hash or len(hist) <= bench_router.cache_last_k


def test_failover_to_other_tier(cluster):
    fi = FaultInjector()
    r = make_router(cluster, strategy="heuristic", benchmark_mode=True,
                    fault_injector=fi)
    fi.fail_next("nano", "boom")
    resp, _, device = r.route_query(
        [{"role": "user", "content": "What is the capital of France"}])
    assert device == "orin" and resp["ok"] is True


def test_failover_disabled_surfaces_error(cluster):
    fi = FaultInjector()
    cfg = dict(PRODUCTION_CFG)
    cfg["enable_failover"] = False
    r = make_router(cluster, strategy="heuristic", config=cfg,
                    benchmark_mode=True, fault_injector=fi)
    fi.set_down("nano", "nano offline")
    resp, _, device = r.route_query(
        [{"role": "user", "content": "What is the capital of France"}])
    assert device == "nano" and resp["ok"] is False
    assert "nano offline" in resp["response"]
    fi.restore("nano")


def test_both_tiers_fail_keeps_primary_error(cluster):
    fi = FaultInjector()
    r = make_router(cluster, strategy="heuristic", benchmark_mode=True,
                    fault_injector=fi)
    fi.set_down("nano", "nano down")
    fi.set_down("orin", "orin down")
    resp, _, device = r.route_query(
        [{"role": "user", "content": "What is the capital of France"}])
    assert resp["ok"] is False and device == "nano"
    assert "nano down" in resp["response"]


def test_perf_feedback_loop(cluster):
    fi = FaultInjector()
    r = make_router(cluster, strategy="perf", benchmark_mode=True,
                    fault_injector=fi)
    hist = [{"role": "user", "content": "hello"}]
    # First query defaults to nano (no stats); make nano fail so its
    # fail-penalty steers subsequent traffic to orin.
    fi.set_down("nano", "nano down")
    r.route_query(hist)
    fi.restore("nano")
    resp, _, device = r.route_query(hist)
    assert device == "orin"
    assert "scores" in resp["routing_reasoning"]


def test_response_cache_production_mode(cluster):
    r = make_router(cluster, strategy="heuristic",
                    config=dict(PRODUCTION_CFG), benchmark_mode=False)
    hist = [{"role": "user", "content": "What is the capital of France"}]
    first, _, _ = r.route_query(hist)
    assert first["cache_hit"] in (False, True)   # routing cache may hit
    second, _, _ = r.route_query(hist)
    assert second["cache_hit"] is True
    assert second["routing_method"] == "response_cache"
    assert second["response"] == first["response"]
    assert second["routing_overhead_ms"] == 0.0


def test_response_cache_disabled_in_benchmark_mode(cluster):
    r = make_router(cluster, strategy="heuristic",
                    config=dict(PRODUCTION_CFG), benchmark_mode=True)
    assert r.enable_response_cache is False


def test_extract_text_shapes(bench_router):
    ex = bench_router._extract_text
    assert ex("  plain  ") == "plain"
    assert ex({"response": "a"}) == "a"
    assert ex({"content": "b"}) == "b"
    assert ex({"message": {"content": "c"}}) == "c"
    assert ex({"error": "E", "detail": "D"}) == "E D"
    assert ex({"response": "  "}) is None
    assert ex(None) is None


def test_routing_engine_failure_falls_back_to_ctx_size(cluster, monkeypatch):
    r = make_router(cluster, strategy="token", benchmark_mode=True)
    monkeypatch.setattr(r.query_router, "route_query",
                        lambda **kw: (_ for _ in ()).throw(RuntimeError("x")))
    small, _, dev_small = r.route_query([{"role": "user", "content": "hi"}])
    assert dev_small == "nano"
    assert small["routing_method"] == "fallback_ctx_size"
    big, _, dev_big = r.route_query(
        [{"role": "user", "content": "w" * 2000}])
    assert dev_big == "orin"


# -- Flask /chat app --------------------------------------------------------

@pytest.fixture(scope="module")
def client(cluster):
    router = Router(strategy="hybrid", config={
        "cache_enabled": True, "enable_response_cache": True,
        "enable_failover": True,
        "weights": {"token": 0.25, "semantic": 0.45, "heuristic": 0.30},
    }, cluster=cluster)
    app = create_app(router=router)
    app.testing = True
    return app.test_client()


def test_chat_contract(client):
    rv = client.post("/chat", json={"message": "What is the capital of France",
                                    "strategy": "hybrid",
                                    "session_id": "s1"})
    assert rv.status_code == 200
    body = rv.get_json()
    for key in ("reply", "device", "reasoning", "method", "confidence",
                "cache_hit", "tokens"):
        assert key in body
    assert body["device"] in ("nano", "orin")


def test_chat_empty_message_400(client):
    rv = client.post("/chat", json={"message": "   "})
    assert rv.status_code == 400
    assert "error" in rv.get_json()


def test_chat_history_roundtrip(client):
    client.post("/chat", json={"message": "hello", "session_id": "s2"})
    rv = client.get("/history?session_id=s2")
    hist = rv.get_json()
    assert hist[0] == {"role": "user", "content": "hello"}
    assert hist[1]["role"] == "assistant"
    rv = client.delete("/history?session_id=s2")
    assert rv.get_json() == {"cleared": "s2"}
    assert client.get("/history?session_id=s2").get_json() == []


def test_chat_history_capped_at_10(client):
    for i in range(8):
        client.post("/chat", json={"message": f"msg {i}", "session_id": "s3"})
    hist = client.get("/history?session_id=s3").get_json()
    assert len(hist) == 10


def test_chat_strategy_mapping_and_switch(client):
    rv = client.post("/chat", json={"message": "hello there friend",
                                    "strategy": "token-counting",
                                    "session_id": "s4"})
    assert rv.get_json()["method"] in ("token", "token_cached",
                                       "response_cache")
    rv = client.post("/chat", json={"message": "hello there friend",
                                    "strategy": "bogus", "session_id": "s4"})
    assert rv.status_code == 500


# -- per-tier /query API ----------------------------------------------------

@pytest.fixture(scope="module")
def tier_client(cluster):
    tiers = build_tiers(cluster, warmup_on_start=False)
    app = create_tier_app("nano", manager=tiers["nano"].server_manager)
    app.testing = True
    return app.test_client()


def test_tier_api_health(tier_client):
    assert tier_client.get("/health").get_json() == {"ok": True}
    assert tier_client.get("/").status_code == 200


def test_tier_api_query_contract(tier_client):
    rv = tier_client.post("/query", json={
        "query": [{"role": "user", "content": "hi"}]})
    assert rv.status_code == 200
    assert "response" in rv.get_json()
    rv = tier_client.post("/query", json={"query": "plain string"})
    assert rv.status_code == 200


def test_tier_api_bad_requests(tier_client):
    assert tier_client.post("/query", json={}).status_code == 400
    assert tier_client.post(
        "/query", json={"query": 42}).status_code == 400


def test_tier_api_num_predict(tier_client):
    rv = tier_client.post("/query", json={"query": "count", "num_predict": 2})
    assert rv.status_code == 200


def test_tier_api_non_numeric_options_400(tier_client):
    rv = tier_client.post("/query", json={"query": "hi", "num_predict": "fast"})
    assert rv.status_code == 400
    rv = tier_client.post("/query", json={"query": "hi", "temperature": "hot"})
    assert rv.status_code == 400


def test_tier_api_temperature_sampling(tier_client):
    # temperature reaches the sampler: repeated hot-sampled calls should not
    # all match the greedy output (512-way categorical vs argmax).
    greedy = tier_client.post(
        "/query", json={"query": "hello", "num_predict": 8}).get_json()
    hot = [tier_client.post(
        "/query", json={"query": "hello", "num_predict": 8,
                        "temperature": 5.0}).get_json()
        for _ in range(3)]
    assert any(h["response"] != greedy["response"] for h in hot)


def test_cors_preflight(client):
    rv = client.open("/chat", method="OPTIONS")
    assert rv.status_code == 204
    assert "POST" in rv.allow_methods


# -- frontend serving (frontend/ static app over the /chat contract) --------

def test_ui_routes_served_with_content_types(cluster):
    app = create_app(router=make_router(cluster))
    c = app.test_client()
    page = c.get("/ui")
    assert page.status_code == 200
    assert "text/html" in page.content_type
    assert "Medibot" in page.text and "app.js" in page.text

    js = c.get("/ui/app.js")
    assert js.status_code == 200
    assert "javascript" in js.content_type
    # The client must speak the reference contract fields.
    for field in ("session_id", "strategy", "cache_hit", "confidence"):
        assert field in js.text

    css = c.get("/ui/style.css")
    assert css.status_code == 200
    assert "text/css" in css.content_type


# -- request timeouts (reference parity: src/models/nano.py:28 (5,180)) -----

class _StubManager:
    """EngineManager stand-in whose engine the test controls."""

    def __init__(self, engine):
        self._engine = engine

    def is_server_running(self):
        return True

    def engine(self):
        return self._engine


def _timeout_tier(timeout):
    import dataclasses
    return dataclasses.replace(tiny_cluster().nano,
                               request_timeout_s=timeout)


def test_request_timeout_returns_reference_error_shape():
    """A device call past tier.request_timeout_s returns the reference
    error-dict shape instead of hanging the serving thread — on a wedged
    chip this is the ONLY way failover/perf-penalty machinery can fire."""
    import time as _t

    from distributed_llm_tpu.serving.tiers import TierClient

    class HangingEngine:
        def generate(self, history, **kw):
            _t.sleep(30)

    client = TierClient(_timeout_tier(0.2), _StubManager(HangingEngine()))
    t0 = _t.monotonic()
    out = client.process("hi")
    assert _t.monotonic() - t0 < 5
    assert "error" in out and "timed out after" in out["error"]


def test_request_timeout_none_disables_cap():
    from distributed_llm_tpu.serving.tiers import TierClient

    class EchoEngine:
        def generate(self, history, **kw):
            class R:
                text = "ok"
            return R()

    client = TierClient(_timeout_tier(None), _StubManager(EchoEngine()))
    assert client.process("hi") == {"response": "ok"}


def test_sequential_engine_calls_stay_serialized():
    """Timeout-abandoned workers must not overlap a later call on a
    sequential engine (no internal locks): the tier lock serializes
    them; the batched engine (concurrent_safe) skips the lock."""
    import threading as _th
    import time as _t

    from distributed_llm_tpu.serving.tiers import TierClient

    class RecordingEngine:
        def __init__(self):
            self.active = 0
            self.max_active = 0
            self._m = _th.Lock()

        def generate(self, history, **kw):
            with self._m:
                self.active += 1
                self.max_active = max(self.max_active, self.active)
            _t.sleep(0.1)
            with self._m:
                self.active -= 1

            class R:
                text = "ok"
            return R()

    eng = RecordingEngine()
    client = TierClient(_timeout_tier(0.02), _StubManager(eng))
    out_a = client.process("a")
    assert "timed out" in out_a["error"]
    # While the abandoned worker is outstanding, new sequential requests
    # fail FAST (no worker spawned — an unbounded backlog of daemon
    # threads draining serially after chip recovery was the failure mode).
    out_b = client.process("b")
    assert "abandoned" in out_b["error"]
    _t.sleep(0.5)                      # let the abandoned worker drain
    assert eng.max_active == 1, "sequential engine saw overlapping calls"
    # Once drained, the tier serves again.
    client.tier = _timeout_tier(5.0)
    assert client.process("c") == {"response": "ok"}
    assert eng.max_active == 1

    class ConcurrentEngine(RecordingEngine):
        concurrent_safe = True

    eng2 = ConcurrentEngine()
    client2 = TierClient(_timeout_tier(0.02), _StubManager(eng2))
    for q in ("a", "b", "c"):
        out = client2.process(q)
        assert "timed out" in out["error"]   # never fail-fast: no serialization
    _t.sleep(0.5)
    assert eng2.max_active > 1, "batched engine should not be serialized"


def test_none_result_returns_error_dict_not_crash():
    """An engine that completes with neither result nor error (stopped/
    abandoned request) must yield the reference error shape — not an
    AttributeError in a daemon worker."""
    from distributed_llm_tpu.serving.tiers import TierClient

    class NoneEngine:
        def generate(self, history, **kw):
            return None

    client = TierClient(_timeout_tier(None), _StubManager(NoneEngine()))
    out = client.process("hi")
    assert "error" in out and "no result" in out["error"]
    # Same guard on the timeout worker path.
    client2 = TierClient(_timeout_tier(5.0), _StubManager(NoneEngine()))
    out2 = client2.process("hi")
    assert "error" in out2 and "no result" in out2["error"]


def test_abandoned_completion_does_not_overwrite_last_result():
    """A timed-out worker that later finishes must not clobber
    last_result with a response nobody received."""
    import threading as _th
    import time as _t

    from distributed_llm_tpu.serving.tiers import TierClient

    release = _th.Event()

    class SlowThenFast:
        def __init__(self):
            self.calls = 0

        def generate(self, history, **kw):
            self.calls += 1
            text = f"answer-{self.calls}"
            if self.calls == 1:
                release.wait(10)       # held until the test lets go

            class R:
                pass
            r = R()
            r.text = text
            return r

    eng = SlowThenFast()
    client = TierClient(_timeout_tier(0.1), _StubManager(eng))
    out = client.process("a")
    assert "timed out" in out["error"]
    release.set()
    _t.sleep(0.5)                      # abandoned worker finishes now
    assert client.last_result is None, \
        "stale abandoned completion overwrote last_result"
    client.tier = _timeout_tier(5.0)
    assert client.process("b") == {"response": "answer-2"}
    assert client.last_result.text == "answer-2"


def test_stream_setup_lock_acquire_is_bounded():
    """process_stream must not block forever behind an abandoned sync
    worker holding the engine lock: past
    request_timeout_s it returns the reference error shape so Router
    stream failover can fire."""
    import time as _t

    from distributed_llm_tpu.serving.tiers import TierClient

    class HangingEngine:
        def generate(self, history, **kw):
            _t.sleep(30)

        def generate_stream(self, history, **kw):
            yield "never"

    client = TierClient(_timeout_tier(0.2), _StubManager(HangingEngine()))
    out = client.process("wedge me")           # abandons a lock-holding worker
    assert "timed out" in out["error"]
    t0 = _t.monotonic()
    stream = client.process_stream("hi")
    assert _t.monotonic() - t0 < 5
    assert isinstance(stream, dict) and "error" in stream
    assert "busy" in stream["error"]


def test_router_fails_over_on_tier_timeout(cluster):
    """End-to-end: nano hangs past its cap, the router serves the query
    on orin (reference failover semantics, src/router.py:277-282)."""
    import dataclasses
    import time as _t

    r = make_router(cluster, strategy="heuristic", benchmark_mode=True)
    nano = r.tiers["nano"]
    nano.server_manager.start_server()
    real_engine = nano.server_manager.engine()

    class Hanging:
        def generate(self, history, **kw):
            _t.sleep(30)

    nano.tier = dataclasses.replace(nano.tier, request_timeout_s=0.2)
    nano.server_manager._engine = Hanging()
    try:
        resp, _, device = r.route_query(
            [{"role": "user", "content": "What is the capital of France"}])
        assert device == "orin" and resp["ok"] is True
    finally:
        nano.server_manager._engine = real_engine


def test_failover_records_primary_failure_in_perf(cluster):
    """The reference feeds perf only for the device that ultimately
    served (router.py:292-295), so failover masked every failure from
    the perf strategy.  We diverge (PARITY.md): the primary's failure is
    recorded too — fail_penalty exists to steer traffic off flaky
    tiers, which matters most when request timeouts mark a wedged one."""
    fi = FaultInjector()
    r = make_router(cluster, strategy="perf", benchmark_mode=True,
                    fault_injector=fi)
    fi.fail_next("nano", "boom")
    resp, _, device = r.route_query(
        [{"role": "user", "content": "hello there"}])   # perf default: nano
    assert device == "orin" and resp["ok"] is True
    strategy = r.query_router.router
    nano_samples = list(strategy.samples["nano"])
    assert nano_samples and nano_samples[-1][2] is False, nano_samples
    orin_samples = list(strategy.samples["orin"])
    assert orin_samples and orin_samples[-1][2] is True, orin_samples


def test_stream_holds_sequential_engine_lock_until_done():
    """A live stream on a sequential engine must exclude sync calls
    (which would interleave with an engine that assumes serialized
    callers); exhaustion releases the lock.  Setup failure and
    unconsumed-handle GC release it too."""
    import gc

    from distributed_llm_tpu.serving.tiers import TierClient

    class FakeHandle:
        result = None

        def __init__(self, deltas):
            self._deltas = deltas

        def __iter__(self):
            yield from self._deltas

    class StreamEngine:
        def generate_stream(self, history, **kw):
            return FakeHandle(["a", "b"])

        def generate(self, history, **kw):
            class R:
                text = "sync"
            return R()

    client = TierClient(_timeout_tier(0.2), _StubManager(StreamEngine()))
    handle = client.process_stream("hi")
    assert not isinstance(handle, dict), handle
    # Lock held: a sync request times out instead of interleaving.
    out = client.process("also hi")
    assert "timed out" in out.get("error", ""), out
    # Delta BOUNDARIES are not contractual (the turn-clip wrapper's
    # hold-back may coalesce them); the concatenated text is.
    assert "".join(handle) == "ab"          # exhaustion releases
    # The timed-out worker drains once the lock frees; wait it out so
    # the next call isn't failed fast as abandoned-outstanding.
    import time as _t
    for _ in range(100):
        if client._abandoned == 0:
            break
        _t.sleep(0.05)
    assert client.process("again") == {"response": "sync"}

    # Unconsumed handle: GC releases.
    handle2 = client.process_stream("hi")
    assert not isinstance(handle2, dict)
    del handle2
    gc.collect()
    assert client.process("after gc") == {"response": "sync"}

    # Setup failure (priming raises): the lock is released once.
    class FailingHandle(FakeHandle):
        def __iter__(self):
            raise RuntimeError("prefill exploded")
            yield  # pragma: no cover

    class FailingStreamEngine(StreamEngine):
        def generate_stream(self, history, **kw):
            return FailingHandle([])

    client2 = TierClient(_timeout_tier(0.2),
                         _StubManager(FailingStreamEngine()))
    err = client2.process_stream("hi")
    assert "prefill exploded" in err["error"]
    assert client2.process_stream("hi")["error"]  # lock free: fails again,
    gc.collect()                                  # not deadlocks


# -- prefix-affinity routing (beyond-reference, production only) ------------

def test_prefix_affinity_override_logic(cluster):
    """Low-confidence decisions flip to the tier holding a meaningful
    parked prefix; confident decisions and trivial prefixes never do."""
    r = make_router(cluster, strategy="heuristic", config=PRODUCTION_CFG)
    assert r.enable_prefix_affinity

    class FakeEngine:
        def __init__(self, n):
            self.n = n

        def prefix_affinity(self, history):
            return self.n

    r.tiers["nano"].server_manager._engine = FakeEngine(0)
    r.tiers["orin"].server_manager._engine = FakeEngine(200)

    hist = [{"role": "user", "content": "and another thing?"}]
    dev, method, why = r._apply_prefix_affinity("nano", 0.5, "heuristic",
                                                "base", hist)
    assert dev == "orin" and method.endswith("+prefix_affinity")
    assert "200-token parked prefix" in why

    # Confident decision: no probe, no flip.
    dev, method, _ = r._apply_prefix_affinity("nano", 0.9, "heuristic",
                                              "base", hist)
    assert dev == "nano" and method == "heuristic"

    # Margin below min_tokens: no flip.
    r.tiers["orin"].server_manager._engine = FakeEngine(10)
    dev, _, _ = r._apply_prefix_affinity("nano", 0.5, "heuristic",
                                         "base", hist)
    assert dev == "nano"

    # UPGRADE-ONLY: a parked prefix on the weaker tier never downgrades
    # an orin decision — locality must not cost capability (measured:
    # the symmetric rule dragged orin-labeled queries to nano).
    r.tiers["nano"].server_manager._engine = FakeEngine(500)
    r.tiers["orin"].server_manager._engine = FakeEngine(0)
    dev, method, _ = r._apply_prefix_affinity("orin", 0.2, "semantic",
                                              "base", hist)
    assert dev == "orin" and method == "semantic"

    # Benchmark mode keeps reference semantics entirely.
    rb = make_router(cluster, strategy="heuristic", benchmark_mode=True,
                     config=PRODUCTION_CFG)
    assert not rb.enable_prefix_affinity


def test_prefix_affinity_end_to_end_with_real_engines(cluster):
    """After a conversation serves on orin, a low-confidence follow-up
    probes the REAL engines' parked prefixes and sticks to orin."""
    r = make_router(cluster, strategy="heuristic", config=PRODUCTION_CFG)
    hist = [{"role": "user", "content":
             "Please implement a merge of two sorted lists and explain "
             "the complexity tradeoffs in detail for me now, covering "
             "stability, allocation strategy, asymptotic and practical "
             "costs, and how you would regression test the function "
             "against adversarial inputs and fuzzed list shapes."}]
    _, _, dev = r.route_query(hist)
    assert dev == "orin"                      # complex → big tier
    res = r.tiers["orin"].last_result
    hist.append({"role": "assistant", "content": res.text})
    hist.append({"role": "user", "content": "and?"})
    dev2, method2, why2 = r._apply_prefix_affinity(
        "nano", 0.5, "heuristic", "base", hist)
    assert dev2 == "orin", (method2, why2)
    assert "+prefix_affinity" in method2


def test_default_cluster_has_one_branch_per_backend(monkeypatch):
    """``default_cluster()`` takes no argument and reads the backend
    alone: the tiny batched tiers on host CPU, ``bench_cluster()`` on an
    accelerator, each with its published checkpoints filled in."""
    import inspect

    import jax

    import distributed_llm_tpu.config as C
    from distributed_llm_tpu.serving import router as R

    assert not inspect.signature(R.default_cluster).parameters
    monkeypatch.setattr(C, "default_checkpoint",
                        lambda preset: f"/ck/{preset}")
    assert R.default_cluster() == C.with_default_checkpoints(
        C.tiny_batched_cluster())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = R.default_cluster()
    assert on_chip == C.with_default_checkpoints(C.bench_cluster())
    assert (on_chip.nano.model_preset, on_chip.orin.checkpoint_path) == (
        "nano_bench", "/ck/orin_bench")


def _old_tuning_table(monkeypatch):
    """A table of the departed tier-tuning overlay, written where its
    loader looked (beside the bench package's modules) and asking for
    everything it could: fp weights, int8 KV, a draft on orin."""
    import os

    import distributed_llm_tpu.bench as bench_pkg
    path = os.path.join(os.path.dirname(bench_pkg.__file__), "tuning.json")
    assert not os.path.exists(path), "the tuning table is back in the tree"
    table = {"backend": "cpu", "tiers": {
        "nano": {"quantize": "none", "kv_quantize": "int8"},
        "orin": {"quantize": "none", "kv_quantize": "int8",
                 "speculative": True}}}
    with open(path, "w") as f:
        json.dump(table, f)
    return path


@pytest.mark.parametrize("steer", [
    lambda mp: None,
    lambda mp: mp.setenv("DLLM_BENCH_SPEC_ORIN", "1"),
    lambda mp: mp.setenv("DLLM_TP", "2"),
    _old_tuning_table,
], ids=["nothing-set", "DLLM_BENCH_SPEC_ORIN=1", "DLLM_TP=2",
        "a-tuning.json-on-disk"])
def test_bench_cluster_reads_no_table_and_no_environment(steer, monkeypatch):
    """The accelerator default is a literal: neither of the departed
    variables nor a tuning table moves ``bench_cluster()`` or the tensor-
    parallel degree a tier asks for."""
    import os

    from distributed_llm_tpu.config import (ClusterConfig, TierConfig,
                                            bench_cluster)
    from distributed_llm_tpu.parallel.mesh import requested_tp

    written = steer(monkeypatch)
    try:
        assert bench_cluster() == ClusterConfig(
            nano=TierConfig(name="nano", model_preset="nano_bench", tp=1,
                            max_new_tokens=64, quantize="int8",
                            decode_batch=8),
            orin=TierConfig(name="orin", model_preset="orin_bench", tp=1,
                            max_new_tokens=128, quantize="int8",
                            decode_batch=4))
        assert [requested_tp(t) for t in bench_cluster().tiers()] == [1, 1]
        assert requested_tp(TierConfig(name="orin", model_preset="orin_test",
                                       tp=4)) == 4
    finally:
        if written:
            os.unlink(written)


def test_stats_measured_tables_names_only_what_is_read(client):
    """GET /stats ``measured_tables``: the backend, and no table (serving
    reads none since ISSUE 49)."""
    assert client.get("/stats").get_json()["measured_tables"] == {
        "backend": "cpu"}


def test_server_main_says_what_it_runs_on_before_serving(monkeypatch,
                                                         caplog, tmp_path):
    """`python -m distributed_llm_tpu`: the compile cache is placed and
    the FIRST log line names platform, device kind/count, the cluster
    chosen and the cache — so a server that came up on the host CPU
    (tiny test tiers) says so before it serves anything."""
    import logging

    from distributed_llm_tpu.serving import app as app_mod

    built = {}

    class StubRouter:
        def __init__(self, **kw):
            built["router"] = kw

    class StubApp:
        def run(self, **kw):
            built["run"] = kw

    prior = jax_cache_dir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(app_mod, "Router", StubRouter)
    monkeypatch.setattr(app_mod, "create_app", lambda router: StubApp())
    monkeypatch.setattr(app_mod, "install_drain_handler", lambda r: True)
    try:
        with caplog.at_level(logging.INFO,
                             logger="distributed_llm_tpu.serving.app"):
            app_mod.main()
        assert jax_cache_dir() == str(tmp_path)
    finally:
        import jax
        jax.config.update("jax_compilation_cache_dir", prior)
    first = caplog.records[0].getMessage()
    assert "platform=cpu" in first and "count=8" in first
    assert "nano=nano_test" in first and "orin=orin_test" in first
    assert str(tmp_path) in first
    assert built["router"]["cluster"].nano.model_preset == "nano_test"
    assert built["run"]["port"] == 8000


def jax_cache_dir():
    import jax
    return jax.config.jax_compilation_cache_dir
