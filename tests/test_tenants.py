"""Per-tenant isolation (ISSUE 17): quota-enforced admission, the
post-paid device-time token bucket, deficit-weighted fair scheduling,
KV/spec budgets, edge validation of tenant_id, bounded tenant metric
labels, per-tenant cost/SLO surfaces, over-quota incidents, and the
quotas-off byte-identity contract.
"""

import dataclasses
import threading

import pytest

from distributed_llm_tpu.config import (TenantQuota, tiny_batched_cluster,
                                        tiny_cluster)
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.obs import Observability
from distributed_llm_tpu.obs.metrics import BoundedLabels
from distributed_llm_tpu.serving.errors import ALLOWED_KEYS, is_error_shape
from distributed_llm_tpu.serving.router import Router
from distributed_llm_tpu.serving.tenants import (DEFAULT_TENANT,
                                                 TenantQuotas, default_quota)


def _tier(**kw):
    return dataclasses.replace(tiny_cluster().nano, **kw)


def _quota_tier(quotas, **kw):
    return _tier(tenant_quotas=quotas, **kw)


# -- TenantQuotas registry ---------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_inflight_cap_and_release():
    tq = TenantQuotas(_quota_tier({"a": TenantQuota(max_inflight=1,
                                                    max_queued=1)}))
    assert tq.try_admit("a") is None
    assert tq.try_admit("a") is None          # the queued seat
    err = tq.try_admit("a")
    assert err is not None and "tenant 'a'" in err and "queue full" in err
    tq.release("a")
    assert tq.try_admit("a") is None          # seat freed
    # Other tenants ride the (unlimited) env default, not a's cap.
    assert tq.try_admit("b") is None
    snap = tq.snapshot()
    assert snap["active"] == {"a": 2, "b": 1}
    assert snap["admitted"] == 4 and snap["rejected"] == 1


def test_device_time_bucket_is_post_paid():
    """Admission is against the CURRENT level; the measured bill debits
    after the fact (level goes negative), and refill re-admits."""
    clock = FakeClock()
    tq = TenantQuotas(
        _quota_tier({"a": TenantQuota(device_ms_per_s=100.0)}), now=clock)
    assert tq.try_admit("a") is None          # burst = 2x rate = 200 ms
    tq.debit("a", 500.0)                      # measured cost >> budget
    tq.release("a")
    err = tq.try_admit("a")
    assert err is not None and "device-time budget exhausted" in err
    # retry_after_s = time-to-positive at 100 ms/s of deficit.
    assert tq.retry_after_s("a") == pytest.approx(3.0, abs=0.1)
    clock.t += 4.0                            # refill past zero
    assert tq.try_admit("a") is None
    # Tenants without a rate budget never hit the bucket.
    tq2 = TenantQuotas(_quota_tier({"b": TenantQuota()}))
    tq2.debit("b", 1e9)
    assert tq2.try_admit("b") is None
    assert tq2.retry_after_s("b") == 1.0


def test_kv_budget_gate():
    tq = TenantQuotas(_quota_tier({"a": TenantQuota(kv_blocks=4)}))
    assert tq.kv_budget("a") == 4 and tq.kv_budget("other") is None
    assert tq.try_admit("a", kv_bill=4.0) is None      # at budget admits
    err = tq.try_admit("a", kv_bill=4.5)
    assert err is not None and "KV demand" in err and "tenant 'a'" in err
    assert tq.try_admit("a", kv_bill=None) is None     # no bill, no gate


def test_default_quota_from_env(monkeypatch):
    monkeypatch.setenv("DLLM_TENANT_MAX_INFLIGHT", "2")
    monkeypatch.setenv("DLLM_TENANT_DEVICE_MS_PER_S", "50.5")
    q = default_quota()
    assert q.max_inflight == 2
    assert q.device_ms_per_s == pytest.approx(50.5)
    assert q.kv_blocks is None and q.spec_gamma_max is None
    tq = TenantQuotas(_quota_tier({}))
    assert tq.try_admit("anyone") is None
    assert tq.try_admit("anyone") is None
    assert "queue full" in tq.try_admit("anyone")
    monkeypatch.delenv("DLLM_TENANT_MAX_INFLIGHT")
    monkeypatch.delenv("DLLM_TENANT_DEVICE_MS_PER_S")
    q = default_quota()
    assert q.max_inflight is None and q.device_ms_per_s is None


def test_quotas_off_constructs_nothing():
    """tenant_quotas=None (the default) never builds a registry: the
    TierClient attribute is None and every gate is a no-op."""
    from distributed_llm_tpu.engine.manager import EngineManager
    from distributed_llm_tpu.serving.tiers import TierClient
    tier = _tier(decode_batch=2)
    client = TierClient(tier, EngineManager(tier, warmup_on_start=False))
    assert client.tenants is None
    assert client._tenant_try_admit(None, "anyone") is None


# -- deficit-weighted round-robin admission order ----------------------------

def _dwrr_engine(quotas):
    # Never started: _next_request is exercised directly (the scheduler
    # thread is the only consumer in production, so no races here).
    return ContinuousBatchingEngine(
        _quota_tier(quotas, decode_batch=2), seed=0)


def _submit_order(engine, tenants):
    from distributed_llm_tpu.engine.batching import _Request
    for i, t in enumerate(tenants):
        engine._queue.put(_Request(history=f"q{i}", max_new_tokens=1,
                                   temperature=0.0, tenant=t))
    order = []
    while True:
        req = engine._next_request()
        if req is None:
            break
        order.append(req.tenant)
    return order


def test_dwrr_interleaves_by_weight():
    """Weight 2 vs 1 admits two of a's requests per one of b's — and the
    order is deterministic for a given arrival interleaving."""
    quotas = {"a": TenantQuota(weight=2.0), "b": TenantQuota(weight=1.0)}
    eng = _dwrr_engine(quotas)
    try:
        order = _submit_order(eng, ["a"] * 4 + ["b"] * 2)
        assert order == ["a", "a", "b", "a", "a", "b"]
        # Deterministic: the same arrivals replay identically.
        assert _submit_order(eng, ["a"] * 4 + ["b"] * 2) == order
    finally:
        eng.stop()


def test_dwrr_untagged_requests_share_the_default_lane():
    eng = _dwrr_engine({"a": TenantQuota(weight=1.0)})
    try:
        order = _submit_order(eng, ["a", None, "a", None])
        assert sorted(o or "default" for o in order) == [
            "a", "a", "default", "default"]
        assert eng.queue_depth() == 0         # lanes fully drained
    finally:
        eng.stop()


def test_quotas_off_queue_is_verbatim_fifo():
    eng = ContinuousBatchingEngine(_tier(decode_batch=2), seed=0)
    try:
        assert eng._tenant_quotas is None
        order = _submit_order(eng, ["b", "a", "b", "a"])
        assert order == ["b", "a", "b", "a"]
        assert eng._tenant_lanes == {}        # DWRR state never touched
    finally:
        eng.stop()


# -- per-tenant spec gamma caps ----------------------------------------------

def test_tenant_gamma_cap_clamps_adaptation():
    from distributed_llm_tpu.engine.batching import _Request
    eng = _dwrr_engine({"capped": TenantQuota(spec_gamma_max=2),
                        "banned": TenantQuota(spec_gamma_max=0)})
    try:
        capped = _Request(history="x", max_new_tokens=1, temperature=0.0,
                          tenant="capped")
        banned = _Request(history="x", max_new_tokens=1, temperature=0.0,
                          tenant="banned")
        free = _Request(history="x", max_new_tokens=1, temperature=0.0,
                        tenant="elsewhere")
        assert eng._tenant_gamma_cap(capped) == 2
        assert eng._tenant_gamma_cap(banned) == 0
        assert eng._tenant_gamma_cap(free) is None
        # Adaptation never exceeds the clamp; cap 0 pins γ at 0.
        assert eng._adapt_gamma(1.0, cap=2) == 2
        assert eng._adapt_gamma(1.0, cap=0) == 0
        # Off-path identity: no cap == the historical curve.
        for ewma in (0.05, 0.3, 0.7, 1.0):
            assert eng._adapt_gamma(ewma, cap=None) == \
                eng._adapt_gamma(ewma)
    finally:
        eng.stop()


def test_gamma_cap_off_when_quotas_off():
    from distributed_llm_tpu.engine.batching import _Request
    eng = ContinuousBatchingEngine(_tier(decode_batch=2), seed=0)
    try:
        req = _Request(history="x", max_new_tokens=1, temperature=0.0,
                       tenant="anyone")
        assert eng._tenant_gamma_cap(req) is None
    finally:
        eng.stop()


# -- per-tenant KV billing ---------------------------------------------------

def test_tenant_kv_blocks_bills_live_and_parked():
    """A finished request's parked prefix keeps billing its tenant
    (tagged entry); an unknown tenant bills zero."""
    eng = ContinuousBatchingEngine(
        _quota_tier({"a": TenantQuota(kv_blocks=64)}, decode_batch=2,
                    max_new_tokens=4), seed=1)
    try:
        eng.generate("tell me about rivers and lakes and streams please",
                     tenant="a")
        bill = eng.tenant_kv_blocks("a")
        assert bill > 0                        # the parked prefix
        assert eng.tenant_kv_blocks("nobody") == 0.0
        # The parked entry is tagged with its owner.
        entries = eng.prefix_cache.entries_snapshot()
        assert entries and entries[0].cache.get("tenant") == "a"
    finally:
        eng.stop()


def test_overquota_tenant_parked_entries_evicted_first():
    """Under pool pressure the over-budget tenant's parked prefix is
    sacrificed before the in-budget tenant's (the pop_oldest match
    predicate), regardless of LRU order."""
    eng = ContinuousBatchingEngine(
        _quota_tier({"hog": TenantQuota(kv_blocks=1),
                     "ok": TenantQuota(kv_blocks=64)},
                    decode_batch=2, max_new_tokens=4, kv_pool_blocks=8),
        seed=1)
    try:
        # hog parks FIRST (oldest in LRU order), ok second.
        eng.generate("tell me about rivers and lakes and streams please",
                     tenant="hog")
        eng.generate("what is the tallest mountain on the continent now",
                     tenant="ok")
        owners = [e.cache.get("tenant")
                  for e in eng.prefix_cache.entries_snapshot()]
        assert owners == ["hog", "ok"]
        assert eng.tenant_kv_blocks("hog") > 1      # over its budget
        # Exhaust the free pool so the next admission must evict.
        grab = eng.allocator.alloc(eng.allocator.available)
        assert grab is not None
        blocks = eng._alloc_evicting(1)
        assert blocks is not None
        owners = [e.cache.get("tenant")
                  for e in eng.prefix_cache.entries_snapshot()]
        assert "hog" not in owners             # the hog's entry went first
        eng.allocator.free(grab + blocks)
    finally:
        eng.stop()


# -- quotas-off byte-identity pin --------------------------------------------

PROBES = ("tell me about rivers and lakes and streams and oceans please",
          "what is the tallest mountain on the continent of asia today")


def test_quotas_off_and_on_outputs_byte_identical():
    """The whole feature defaults OFF and must be invisible: the same
    greedy requests produce identical token ids with quotas off and
    with (non-binding) quotas on."""
    ids = {}
    for mode, quotas in (("off", None),
                         ("on", {"t0": TenantQuota(max_inflight=8,
                                                   kv_blocks=1024,
                                                   weight=2.0)})):
        eng = ContinuousBatchingEngine(
            _tier(decode_batch=2, max_new_tokens=24, tenant_quotas=quotas),
            seed=1)
        try:
            ids[mode] = [tuple(eng.generate(p, tenant="t0").token_ids)
                         for p in PROBES]
        finally:
            eng.stop()
    assert ids["off"] == ids["on"]


def test_flooders_quota_sheds_the_flooder_never_the_quiet_tenant():
    """Noisy neighbour: a tenant held to one seat and no queue on BOTH
    tiers floods from three closed-loop clients beside a quiet tenant on
    the unset default.  Every quiet request is answered, and every
    tenant-shaped rejection lands on the flooder (failover cannot launder
    the flood onto the other tier)."""
    flood = {"flood": TenantQuota(weight=0.25, max_inflight=1, max_queued=0)}
    base = tiny_batched_cluster(nano_slots=2, orin_slots=2)
    cluster = dataclasses.replace(
        base,
        nano=dataclasses.replace(base.nano, tenant_quotas=flood),
        orin=dataclasses.replace(base.orin, tenant_quotas=flood))
    obs = Observability(slow_ms=None)
    router = Router(strategy="heuristic", benchmark_mode=True,
                    cluster=cluster, observability=obs)
    answers = {"flood": [], "quiet": []}

    def client(tenant, i):
        for turn in range(3):
            doc, _, _dev = router.route_query(
                [{"role": "user",
                  "content": f"{tenant} client {i} turn {turn}: tell me "
                             f"about rivers and lakes and oceans please"}],
                tenant_id=tenant)
            answers[tenant].append(bool(doc.get("ok")))

    try:
        for tier in router.tiers.values():
            tier.server_manager.start_server()
        threads = [threading.Thread(target=client, args=("flood", i),
                                    daemon=True) for i in range(3)]
        threads.append(threading.Thread(target=client, args=("quiet", 0),
                                        daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        for tier in router.tiers.values():
            tier.server_manager.stop_server()
    assert answers["quiet"] == [True] * 3
    assert len(answers["flood"]) == 9 and any(answers["flood"])
    fam = obs.metrics.get("dllm_tenant_rejected_total")
    rejected = {tenant: c.value
                for (_tier_name, tenant), c in fam.children().items()}
    assert set(rejected) == {"flood"} and rejected["flood"] >= 1, rejected


# -- serving edge: tenant_id validation and plumbing -------------------------

@pytest.fixture(scope="module")
def quota_app():
    """App over a cluster whose tiers give tenant 'blocked' zero seats
    (every request sheds on both tiers) and everyone else the
    unlimited default."""
    from distributed_llm_tpu.serving.app import create_app
    quotas = {"blocked": TenantQuota(max_inflight=0)}
    base = tiny_batched_cluster()
    cluster = dataclasses.replace(
        base,
        nano=dataclasses.replace(base.nano, tenant_quotas=quotas),
        orin=dataclasses.replace(base.orin, tenant_quotas=quotas))
    obs = Observability(slow_ms=0.0)
    router = Router(strategy="heuristic", benchmark_mode=True,
                    cluster=cluster, observability=obs)
    app = create_app(router=router)
    client = app.test_client()
    yield client, router, obs
    for tier in router.tiers.values():
        tier.server_manager.stop_server()


def test_tenant_id_validation(quota_app):
    client, _router, _obs = quota_app
    for bad, why in ((123, "non-empty string"), ("", "non-empty string"),
                     ("x" * 65, "exceeds 64 characters"),
                     ("evil\x00tenant", "control characters"),
                     ("two\nlines", "control characters")):
        resp = client.post("/chat", json={"message": "hi",
                                          "tenant_id": bad})
        assert resp.status_code == 400, (bad, resp.status_code)
        doc = resp.get_json()
        assert is_error_shape(doc) and set(doc) <= ALLOWED_KEYS
        assert why in doc["error"], (bad, doc)


def test_tenant_rejection_surfaces_with_retry_hint(quota_app):
    _client, router, obs = quota_app
    doc, _, _dev = router.route_query(
        [{"role": "user", "content": "hello there"}], tenant_id="blocked")
    assert doc["ok"] is False
    raw = doc["raw"]
    assert is_error_shape(raw) and set(raw) <= ALLOWED_KEYS
    assert "tenant 'blocked'" in raw["error"]
    assert raw.get("retry_after_s", 0) > 0
    # Both tiers shed (failover cannot launder a tenant quota).
    fam = obs.metrics.get("dllm_tenant_rejected_total")
    by_tier = {labels: c.value for labels, c in fam.children().items()}
    assert sum(v for (tier, t), v in by_tier.items()
               if t == "blocked") >= 2


def test_absent_tenant_bills_default_and_serves(quota_app):
    client, router, obs = quota_app
    resp = client.post("/chat", json={"message": "short question",
                                      "session_id": "sess-t"})
    assert resp.status_code == 200
    assert resp.get_json()["reply"]
    # The request admitted against (and released) the shared default
    # tenant's quota on whichever tier served it.
    admitted = sum(tc.tenants.snapshot()["admitted"]
                   for tc in router.tiers.values())
    assert admitted >= 1
    assert DEFAULT_TENANT != ""               # sanity on the constant


def test_overquota_incident_names_the_tenant(quota_app):
    client, router, obs = quota_app
    client.post("/chat", json={"message": "hello again",
                               "tenant_id": "blocked"})
    incidents = [e for e in obs.recorder.snapshot()
                 if e.get("reason") == "tenant_overquota"]
    assert incidents, "no tenant_overquota incident recorded"
    inc = incidents[0]["incident"]
    assert inc["tenant"] == "blocked"
    assert "tenant 'blocked'" in inc["first_reason"]
    assert inc["open"] is True                 # never completed a request
    fam = obs.metrics.get("dllm_flight_records_total")
    assert fam.labels("tenant_overquota").value >= 1


def test_incident_closes_on_next_completed_request():
    """The falling edge: a completed request finalizes the tenant's open
    incident with its rejection count."""
    r = Router.__new__(Router)
    r._cost_lock = threading.Lock()
    r._tenant_incidents = {}
    r._session_label_cap = 4
    r.obs = Observability(slow_ms=0.0)
    r._tenant_incident_edge("t1", rejected=True, which="nano",
                            reason="tenant 't1' queue full")
    r._tenant_incident_edge("t1", rejected=True, which="nano",
                            reason="tenant 't1' queue full")
    (entry,) = [e for e in r.obs.recorder.snapshot()
                if e.get("reason") == "tenant_overquota"]
    assert entry["incident"]["open"] is True
    r._tenant_incident_edge("t1", rejected=False)
    (entry,) = [e for e in r.obs.recorder.snapshot()
                if e.get("reason") == "tenant_overquota"]
    assert entry["incident"]["open"] is False
    assert entry["incident"]["rejections_while_open"] == 2
    # Cap: past _session_label_cap distinct tenants, no new incidents.
    for i in range(10):
        r._tenant_incident_edge(f"flood{i}", rejected=True, which="nano",
                                reason=f"tenant 'flood{i}' queue full")
    assert len(r._tenant_incidents) <= 4


def test_stats_carries_tenant_rows_and_quota_snapshot(quota_app):
    client, router, obs = quota_app
    resp = client.post("/chat", json={"message": "a question for costs",
                                      "tenant_id": "payer",
                                      "session_id": "sess-cost"})
    assert resp.status_code == 200
    stats = client.get("/stats").get_json()
    # The quota registry snapshot rides each quota-ON tier entry.
    nano = stats["tiers"]["nano"]
    assert "tenants" in nano and "blocked" in nano["tenants"]["tenants"]
    # The cost ledger rows are (tier, strategy, session, TENANT)-keyed.
    rows = stats["cost"]
    assert rows and all("tenant" in row for row in rows)
    assert any(row["tenant"] == "payer" for row in rows)
    # The per-tenant metric families carry the billed totals.
    fam = obs.metrics.get("dllm_tenant_device_time_ms_total")
    assert any(t == "payer" and c.value > 0
               for (tier, t), c in fam.children().items())
    # SLO goodput window has a per-tenant dimension.
    slo = router.slo.snapshot()
    assert "payer" in slo["tenants"]
    assert obs.metrics.get("dllm_tenant_goodput").labels(
        "payer").value == 1.0


def test_tenant_debit_reaches_token_bucket(quota_app):
    """The measured device-time bill lands in the serving tier's bucket
    (post-paid billing wired end to end)."""
    client, router, obs = quota_app
    resp = client.post("/chat", json={"message": "bill this request",
                                      "tenant_id": "billed"})
    assert resp.status_code == 200
    # No rate budget configured -> no bucket entries; the debit path
    # still ran (covered by the unit test) and the cost families grew.
    fam = obs.metrics.get("dllm_tenant_device_time_ms_total")
    assert any(t == "billed" for (tier, t), c in fam.children().items())


# -- bounded tenant labels ---------------------------------------------------

def test_bounded_labels_truncate_and_overflow():
    bl = BoundedLabels(cap=4)
    assert bl.label(None) == "-" and bl.label("") == "-"
    labels = {bl.label(f"t{i}") for i in range(10)}
    assert labels == {"t0", "t1", "t2", "t3", "~overflow"}
    assert bl.label("t2") == "t2"              # known keeps its label
    assert len(bl.label("x" * 500)) <= 64


def test_tenant_flood_cannot_grow_metrics():
    """An adversarial flood of distinct tenant ids aggregates under
    '~overflow': the /metrics label space stays bounded."""
    obs = Observability()
    for i in range(600):
        lbl = obs.tenant_labels.label(f"tenant-{i}")
        obs.m.tenant_goodput_g.labels(lbl).set(1.0)
        obs.m.tenant_inflight_g.labels("nano", lbl).set(1)
    for fam_name, bound in (("dllm_tenant_goodput", 257),
                            ("dllm_tenant_inflight", 257)):
        fam = obs.metrics.get(fam_name)
        assert len(fam.children()) <= bound, fam_name
