"""Router — the serving orchestration pipeline.

Reference parity: src/router.py.  Same constructor signature, same
``route_query(history) -> (response_dict, tokens, device)`` contract, same
response-dict keys, and the same pipeline stages:

  0) response-cache check (production mode only; key = strategy + query text,
     deliberately context-independent — reference: src/router.py:57-59,179)
  1) routing decision via QueryRouter, with context-size threshold fallback
     if the routing engine raises (src/router.py:258-270)
  2) tier inference + one-shot failover to the other tier on an error-shaped
     response (src/router.py:277-282)
  3) text normalization + token count
  4) perf feedback into the perf strategy (src/router.py:292-295)
  5) response-cache store

What changed underneath: tiers are in-process TPU engines on chip submeshes
(serving/tiers.py) instead of SSH-tunneled Jetson boards, so `_run_device`
is a function call, not an HTTP POST.
"""

from __future__ import annotations

import hashlib
import logging
import random
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from ..config import (ClusterConfig, bench_cluster, resolve_config,
                      tiny_cluster)
from ..config_registry import env_float, env_int, env_str
from ..obs import Observability, get_observability
from ..obs import spans as obs_spans
from ..obs.metrics import breaker_state_value
from ..obs.sampler import SystemStateSampler
from ..obs.slo import SLOMonitor
from ..obs.spans import current_trace, use_trace
from ..routing.engine import QueryRouter
from ..routing.token_counter import TokenCounter
from ..utils.faults import FaultInjector
from .errors import is_error_shape
from .tenants import DEFAULT_TENANT
from .tiers import TierClient, build_tiers

logger = logging.getLogger(__name__)

# Error-shape substrings the bounded retry treats as TRANSIENT (a fresh
# attempt on the same tier plausibly succeeds in milliseconds): connection-
# level races and an engine that shut down mid-flight.  Deliberately NOT
# timeouts — a timed-out call already consumed its whole request budget,
# and retrying it would double the client's wait for the same outcome —
# and NOT admission rejections, where the queue is full and immediate
# re-entry would only re-reject (failover is the productive move).
_TRANSIENT_MARKERS = (
    "connection refused",
    "connection reset",
    "reset by peer",
    "temporarily unavailable",
    "engine returned no result",
    "(transient)",
)


def default_cluster() -> ClusterConfig:
    """``bench_cluster()`` on an accelerator, the tiny batched tiers on
    host CPU (the unit suite builds ``tiny_cluster()`` directly and keeps
    the cheaper sequential warmup).  Either way the tiers serve published
    pretrained weights when ``checkpoints/<preset>`` exists
    (training/pretrain.py)."""
    from ..config import tiny_batched_cluster, with_default_checkpoints
    if jax.default_backend() != "cpu":
        return with_default_checkpoints(bench_cluster())
    return with_default_checkpoints(tiny_batched_cluster())


class Router:
    def __init__(
        self,
        strategy: str = "hybrid",
        config: Optional[Dict[str, Any]] = None,
        threshold_fallback: int = 100,
        benchmark_mode: bool = False,
        cluster: Optional[ClusterConfig] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        fault_injector: Optional[FaultInjector] = None,
        observability: Optional[Observability] = None,
    ):
        """strategy: "token" | "semantic" | "heuristic" | "hybrid" | "perf"
        benchmark_mode: True → BENCHMARK_CFG (cache off), False →
        PRODUCTION_CFG, unless ``config`` overrides (src/router.py:37-40).
        observability: metric/trace/flight-recorder bundle (obs/); None =
        the process-global default — injectable so the benchmark and tests
        read registries no other traffic writes to."""
        self.token_counter = TokenCounter()
        self.obs = (observability if observability is not None
                    else get_observability())
        self.threshold_fallback = threshold_fallback
        self.benchmark_mode = benchmark_mode
        self.config = resolve_config(config, benchmark_mode)

        self.cluster = cluster or default_cluster()
        self.faults = fault_injector
        self.tiers: Dict[str, TierClient] = build_tiers(
            self.cluster, devices=devices, fault_injector=fault_injector)
        # Reference attribute surface (tester uses router.nano.server_manager)
        self.nano = self.tiers["nano"]
        self.orin = self.tiers["orin"]

        self.query_router = QueryRouter(strategy=strategy, config=self.config)

        # Per-tier circuit breaker (serving/breaker.py): consulted before
        # dispatch so an OPEN tier sheds traffic in microseconds instead
        # of each request discovering the outage via a timeout.
        # breaker_failures=0 in the cluster disables it (pure reference
        # per-call failover semantics).
        self.breaker = None
        if getattr(self.cluster, "breaker_failures", 0):
            from .breaker import CircuitBreaker
            self.breaker = CircuitBreaker(
                [t.name for t in self.cluster.tiers()],
                failure_threshold=self.cluster.breaker_failures,
                cooldown_s=self.cluster.breaker_cooldown_s,
                on_transition=self._obs_breaker_transition)
            # Export a closed (0) state sample per tier up front: a
            # dashboard must read 0 for a healthy breaker, not "no
            # series" — absence would be indistinguishable from the
            # breaker being disabled.
            for t in self.cluster.tiers():
                self.obs.m.breaker_state.labels(t.name).set(0)
        # Bounded retry for transient error shapes (_TRANSIENT_MARKERS):
        # budgeted against the dispatching tier's request_timeout_s so
        # retry + failover never exceed the reference's per-request cap.
        self.retry_attempts = max(0, int(getattr(self.cluster,
                                                 "retry_attempts", 0)))
        self.retry_backoff_s = float(getattr(self.cluster,
                                             "retry_backoff_s", 0.05))
        self.degraded_served = 0       # both-tiers-open responses served
        # Graceful drain (drain()): once True the serving edge
        # (serving/app.py) answers 503 + retry_after_s and no new request
        # enters the pipeline; in-flight requests finish normally.
        self.draining = False

        # SLO goodput monitor (obs/slo.py): per-(strategy, tier) sliding-
        # window goodput + overload incidents, fed ONLY from
        # _finish_request (the obs_discipline lint pins the single feed
        # site).  Targets come from each tier's slo_ttft_ms/slo_tbt_ms,
        # globally overridable via DLLM_SLO_TTFT_MS / DLLM_SLO_TBT_MS.
        self.slo = SLOMonitor(self._slo_targets(), metrics=self.obs.m,
                              recorder=self.obs.recorder,
                              timeline=self._timeline_tail)
        # Continuous system-state timeline (obs/sampler.py): a lazy
        # daemon thread (started at first request, stopped by drain())
        # sampling per-tier queue/slot/KV/breaker/tick state every
        # DLLM_OBS_SAMPLE_MS into a bounded ring; '0' disables it.
        self.sampler: Optional[SystemStateSampler] = None
        sample_ms = env_float("DLLM_OBS_SAMPLE_MS", 250.0)
        if sample_ms > 0:
            self.sampler = SystemStateSampler(
                self._sampler_collect, metrics=self.obs.m,
                period_s=sample_ms / 1000.0,
                capacity=env_int("DLLM_OBS_TIMELINE_SAMPLES", 240))

        # What export_tick_totals last saw per (family, engine label,
        # further labels), so its counters survive an engine rebuilt
        # from 0.
        self._tick_totals_lock = threading.Lock()
        self._tick_totals_seen: Dict[Tuple[Any, ...], float] = {}

        # Bounded per-(tier, strategy, session) cost ledger (ISSUE 11):
        # the GET /stats-inspectable aggregate of the attribution the
        # _finish_request exit feeds to the dllm_device_time_ms_total /
        # dllm_kv_block_ticks_total families.  Insertion-ordered with
        # oldest-key eviction past the cap, so a session flood cannot
        # grow it without bound (the metric families keep the full
        # label space; this is the one-call operator view).
        self._cost_lock = threading.Lock()
        self._cost_ledger: "Dict[Tuple[str, str, str], Dict[str, float]]" \
            = {}
        self._cost_ledger_cap = 256
        # Session METRIC-LABEL guard: session_id is client-controlled
        # at the /chat edge, and a Prometheus label value mints a
        # permanent counter child — without a bound, one adversarial
        # client (or just organic session churn) grows the registry and
        # the /metrics payload forever.  First N distinct sessions keep
        # their own label (truncated); the rest aggregate under
        # "~overflow".  The ledger evicts; label children cannot.
        self._session_labels: set = set()
        self._session_label_cap = 256
        # Tenant over-quota incident edge (ISSUE 17): the FIRST quota
        # rejection for a tenant opens a flight-recorder incident naming
        # it (the over-quota tenant that triggered shedding is exactly
        # what the noisy-neighbor post-mortem needs); a later ADMITTED
        # request from the same tenant finalizes it with the rejection
        # count absorbed meanwhile.  Bounded: at most
        # ``_session_label_cap`` distinct open-tenant slots ever.
        self._tenant_incidents: Dict[str, Dict[str, Any]] = {}

        self.enable_response_cache = (
            not benchmark_mode
            and bool(self.config.get("enable_response_cache", False)))
        self.cache_last_k = int(self.config.get("cache_last_k", 6))
        self.enable_failover = bool(self.config.get("enable_failover", True))
        # Prefix-affinity routing (production only, beyond-reference): a
        # low-confidence decision is steered to the tier that already
        # holds this conversation's parked KV prefix — a cold re-prefill
        # elsewhere throws away an O(history) cache the engines worked
        # to keep.  Labeled-accuracy benchmarks keep reference semantics
        # (off in benchmark_mode and in BENCHMARK_CFG).
        self.enable_prefix_affinity = (
            not benchmark_mode
            and bool(self.config.get("enable_prefix_affinity", False)))
        self.prefix_affinity_min_confidence = float(
            self.config.get("prefix_affinity_min_confidence", 0.75))
        self.prefix_affinity_min_tokens = int(
            self.config.get("prefix_affinity_min_tokens", 32))
        self.prefix_affinity_overrides = 0
        self._response_store: Dict[str, Dict[str, Any]] = {}

        # Continuous liveness probing + ICI health exchange (serving/
        # health.py) — off by default to keep bench runs deterministic.
        self.health_monitor = None
        if self.config.get("enable_health_monitor", False):
            from .health import HealthMonitor
            self.health_monitor = HealthMonitor(
                self,
                interval_s=float(self.config.get("health_interval_s", 5.0)),
                mesh=self.config.get("health_mesh"))
            self.health_monitor.start()

        # Elastic capacity (serving/autoscaler.py, ISSUE 18): one
        # control loop per ARMED tier (TierConfig.autoscale), actuating
        # replica membership from the SLO/queue/shed signals above.
        # The DLLM_AUTOSCALE=0 kill switch — or simply no armed tier —
        # builds nothing: the static PR 12 membership path stays
        # byte-identical (pinned by test).
        self.autoscalers: Dict[str, Any] = {}
        if (env_str("DLLM_AUTOSCALE", "1") or "1") != "0":
            from .autoscaler import ReplicaAutoscaler
            for t in self.cluster.tiers():
                client = self.tiers.get(t.name)
                if (getattr(t, "autoscale", False)
                        and callable(getattr(client, "scale_to", None))):
                    scaler = ReplicaAutoscaler(
                        t.name, t, client, self.slo, metrics=self.obs.m)
                    scaler.start()
                    self.autoscalers[t.name] = scaler

    # -- back-compat (src/router.py:65-67) ---------------------------------

    def set_threshold(self, threshold: int) -> None:
        self.threshold_fallback = threshold

    # -- graceful drain ----------------------------------------------------

    def drain_retry_after_s(self) -> float:
        """Client retry hint while draining: the longest tier drain
        deadline (past it the process is gone or restarted)."""
        vals = []
        for tier in self.tiers.values():
            cfg = getattr(tier, "tier", None)
            val = getattr(cfg, "drain_timeout_s", None)
            if val:
                vals.append(float(val))
        return round(max(vals), 2) if vals else 30.0

    def drain(self, timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Graceful shutdown of the whole router (SIGTERM path): flip the
        serving edge to 503 (serving/app.py checks ``draining``), stop
        the health monitor (a drain must not race an auto-restart), then
        drain every tier concurrently — each stops admitting, lets its
        in-flight requests finish under ``drain_timeout_s``, and stops.
        Idempotent; returns the per-tier drain summaries."""
        self.draining = True
        if self.health_monitor is not None:
            try:
                self.health_monitor.stop()
            except Exception:
                pass
        # Autoscalers stop BEFORE the tier drains fan out: a controller
        # mid-tick must not actuate membership against a draining tier.
        for scaler in getattr(self, "autoscalers", {}).values():
            try:
                scaler.stop()
            except Exception:
                pass
        # The state sampler dies with the router: a drained process must
        # not keep a timeline thread alive (it is a daemon either way,
        # but stop() makes the shutdown clean and testable).
        if self.sampler is not None:
            try:
                self.sampler.stop()
            except Exception:
                pass
        results: Dict[str, Any] = {}
        cap = (timeout_s if timeout_s is not None
               else self.drain_retry_after_s()) + 30.0
        threads = []
        for name, tier in self.tiers.items():
            fn = getattr(tier.server_manager, "drain", None)
            if not callable(fn):
                # Managers without a drain (remote tiers) still get
                # STOPPED: the pre-drain shutdown path killed their
                # spawned processes, and graceful must not leak them.
                fn, label = tier.server_manager.stop_server, "stopped"
            else:
                label = None

            def _drain(name=name, fn=fn, label=label):
                try:
                    out = fn() if label else fn(timeout_s)
                    results[name] = (out if label is None
                                     else {"draining_started": False,
                                           label: True})
                except Exception as exc:
                    results[name] = {"error": f"Request failed: {exc}"}

            t = threading.Thread(target=_drain, daemon=True,
                                 name=f"drain-{name}")
            threads.append(t)
            t.start()
        deadline = time.monotonic() + cap
        for t in threads:
            # Bounded even against a wedged stop_server: the process is
            # exiting, and a hung drain must not block the signal path.
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        logger.info("router drain complete: %s", results)
        return results

    # -- observability plumbing (obs/) -------------------------------------

    def _obs_breaker_transition(self, tier: str, old: str, new: str) -> None:
        """Breaker state changes → transition counter + state gauge."""
        m = self.obs.m
        m.breaker_transitions.labels(tier, new).inc()
        m.breaker_state.labels(tier).set(breaker_state_value(new))

    def _slo_targets(self) -> Dict[str, Tuple[Optional[float],
                                              Optional[float]]]:
        """Per-tier (slo_ttft_ms, slo_tbt_ms) targets for the goodput
        monitor: the tier's configured values, with the DLLM_SLO_* env
        overrides winning globally when set (an operator re-judging a
        live box against a tighter SLO must not need a config rebuild)."""
        def parse(raw: Optional[str]) -> Optional[float]:
            if raw is None or not str(raw).strip():
                return None
            try:
                return float(raw)
            except ValueError:
                return None                  # garbage never loses the run

        o_ttft = parse(env_str("DLLM_SLO_TTFT_MS"))
        o_tbt = parse(env_str("DLLM_SLO_TBT_MS"))
        return {
            t.name: (o_ttft if o_ttft is not None
                     else getattr(t, "slo_ttft_ms", None),
                     o_tbt if o_tbt is not None
                     else getattr(t, "slo_tbt_ms", None))
            for t in self.cluster.tiers()
        }

    def _ensure_sampler(self) -> None:
        """Lazy sampler start at first request: routers that never serve
        (the unit suite builds hundreds) must not each spawn a thread."""
        s = self.sampler
        if s is not None and not s.running and not self.draining:
            s.start()

    def _timeline_tail(self, n: int = 40) -> list:
        s = self.sampler
        return s.tail(n) if s is not None else []

    def timeline_snapshot(self) -> list:
        """The GET /stats?timeline=1 body: the full timeline ring,
        sampling once on demand when the ring is empty (an idle router
        still answers with its CURRENT state, not an empty list)."""
        s = self.sampler
        if s is None:
            return []
        if not len(s):
            try:
                s.sample_once()
            except Exception:
                pass
        return s.snapshot()

    def _sampler_collect(self) -> Dict[str, Dict[str, Any]]:  # dllm-lint: hot-path
        """One timeline sample's per-tier state.  Lock-free / own-locked
        in-memory reads ONLY (load_snapshot, kv_stats, the tick ring,
        the draining flag) — never manager.health(), and never anything
        touching the lifecycle lock a mid-compile engine holds for
        minutes: the sampler must keep sampling THROUGH the states it
        exists to explain.  Hot-path root for the transfer lint (the
        callback is invoked through a callable value, which the static
        call graph cannot follow — so it is annotated in its own
        right)."""
        out: Dict[str, Dict[str, Any]] = {}
        breaker_snap = (self.breaker.snapshot()
                        if self.breaker is not None else {})
        for name, tier in self.tiers.items():
            st: Dict[str, Any] = {}
            snap_fn = getattr(tier, "load_snapshot", None)
            if callable(snap_fn):
                try:
                    st.update(snap_fn())
                except Exception:
                    pass
            mgr = tier.server_manager
            subs = getattr(mgr, "live_engines", None)
            if callable(subs):
                # Replicated tier (ISSUE 12): the tier-level entry reads
                # the AGGREGATE kv picture from the ReplicaSetManager
                # (summed pools, max dedup) plus the healthy-capacity
                # fraction; each replica then gets its OWN entry keyed
                # "tier/rN" so every gauge family grows a per-replica
                # series and the timeline carries the breakdown.  ONE
                # kv_stats pass per sample: the aggregate call already
                # returns the per-replica breakdown, which the replica
                # entries reuse instead of re-reading each pool.
                agg_kv = None
                kv_fn = getattr(mgr, "kv_stats", None)
                if callable(kv_fn):
                    try:
                        agg_kv = kv_fn()
                    except Exception:
                        agg_kv = None
                st.update(self._collect_engine_state(mgr, kv=agg_kv))
                healthy_fn = getattr(tier, "healthy_replicas", None)
                if callable(healthy_fn):
                    try:
                        st["replica_healthy"] = int(healthy_fn())
                    except Exception:
                        pass
                # Keyed by replica NAME, not position: dynamic
                # membership (ISSUE 18) removes members mid-run, and a
                # positional lookup would pin the wrong manager's
                # draining flag on the survivors.
                items_fn = getattr(mgr, "replica_items", None)
                mgr_by_key = ({f"r{rid}": sub for rid, sub in items_fn()}
                              if callable(items_fn)
                              else {f"r{i}": sub for i, sub in
                                    enumerate(mgr.replica_managers())})
                rb = getattr(tier, "breaker", None)
                st["replica_count"] = len(mgr_by_key)
                rep_kv = (agg_kv or {}).get("replicas") or {}
                for key, engine in subs():
                    rst = self._collect_engine_state(
                        engine, kv=rep_kv.get(key))
                    slots_fn = getattr(engine, "slot_stats", None)
                    if callable(slots_fn):
                        try:
                            ss = slots_fn()
                            rst["queue_depth"] = ss.get("queue_depth")
                            rst["active_slots"] = ss.get("active_slots")
                            rst["max_slots"] = ss.get("max_slots")
                        except Exception:
                            pass
                    sub = mgr_by_key.get(key)
                    if sub is not None:
                        rst["draining"] = bool(sub.draining)
                    if rb is not None:
                        rst["breaker"] = rb.state(key)
                    out[f"{name}/{key}"] = rst
            else:
                engine = getattr(mgr, "_engine", None)
                st.update(self._collect_engine_state(engine))
            st["draining"] = bool(getattr(mgr, "draining", False))
            b = breaker_snap.get(name)
            if b is not None:
                st["breaker"] = b.get("state")
            out[name] = st
        try:
            self.export_tick_totals()
        except Exception:
            pass
        return out

    _KV_FETCH = object()      # sentinel: "read kv_stats off the engine"

    @staticmethod
    def _collect_engine_state(engine, kv=_KV_FETCH
                              ) -> Dict[str, Any]:  # dllm-lint: hot-path
        """One engine's (or a ReplicaSetManager aggregate's) sampler
        fields — the per-entry half of ``_sampler_collect``, shared by
        the flat tier path, the replicated tier-level aggregate, and the
        per-replica entries.  ``kv`` overrides the kv_stats read with a
        precomputed dict (or None = no pool) so the replicated path pays
        ONE pool read per sample, not two.  Same lock-free discipline:
        advisory own-locked reads only, never the lifecycle lock."""
        st: Dict[str, Any] = {}
        ks = None
        if kv is Router._KV_FETCH:
            kv_fn = getattr(engine, "kv_stats", None)
            if callable(kv_fn):
                try:
                    ks = kv_fn()
                except Exception:
                    ks = None
        else:
            ks = kv
        if isinstance(ks, dict) and ks:
            try:
                st["kv_free_blocks"] = ks.get("free_blocks")
                st["kv_reclaimable_blocks"] = ks.get(
                    "reclaimable_blocks")
                # Shared-prefix KV (ISSUE 10): physical blocks with
                # multiple holders and the dedup factor — the
                # dllm_kv_shared_blocks / dllm_kv_dedup_ratio
                # gauges' source series.
                st["kv_shared_blocks"] = ks.get("shared_blocks", 0)
                st["kv_dedup_ratio"] = ks.get("dedup_ratio", 1.0)
                st["preempted_total"] = ks.get("preempted_total", 0)
                # Hierarchical-KV spill tier (ISSUE 14): host-side
                # occupancy + promotion backlog ride the timeline so a
                # degraded warm-hit rate is diagnosable from the same
                # flight-recorder slice as the pool pressure it caused.
                if "host_blocks" in ks:
                    st["kv_host_blocks"] = ks.get("host_blocks")
                    st["kv_host_bytes"] = ks.get("host_bytes")
                    st["kv_promote_backlog"] = ks.get(
                        "promote_backlog_blocks", 0)
                # Chunked-prefill backlog (PR 9): prompt tokens of
                # the in-flight prefill not yet absorbed — the
                # dllm_prefill_backlog gauge's source series.
                st["prefill_backlog_tokens"] = ks.get(
                    "prefill_backlog_tokens", 0)
            except Exception:
                pass
        tick_fn = getattr(engine, "tick_stats", None)
        if callable(tick_fn):
            try:
                st["decode_tick_p50_ms"] = tick_fn().get("p50_ms")
            except Exception:
                pass
        # Batched speculation (ISSUE 15): the engine-lifetime acceptance
        # ratio — the dllm_spec_accept_ratio gauge's source series
        # (absent until the first draft so the gauge never fakes a 0).
        spec_fn = getattr(engine, "spec_stats", None)
        if callable(spec_fn):
            try:
                ss = spec_fn()
                if ss.get("enabled") and ss.get("accept_ratio") is not None:
                    st["spec_accept_ratio"] = ss["accept_ratio"]
            except Exception:
                pass
        # Tick-phase profiler (ISSUE 11): per-phase p50 self-times
        # over the ring's recent tail + the coverage fraction —
        # advisory ring reads, bounded to the last 128 records so
        # the sampler's <1 ms budget holds as rings grow.
        prof = getattr(engine, "profiler", None)
        if prof is not None and getattr(prof, "enabled", False):
            try:
                ps = prof.sampled_phases(last=128)
                st["tick_phases"] = ps["tick_phases"]
                st["profile_coverage"] = ps["coverage"]
            except Exception:
                pass
        return st

    def _session_label(self, raw: Any) -> str:
        """The bounded metric-label form of a client session id: '-'
        when absent, truncated to 64 chars, and capped at
        ``_session_label_cap`` DISTINCT values per router — later
        sessions aggregate under '~overflow' so a label-minting client
        cannot grow the metric registry without bound."""
        if not raw:
            return "-"
        s = str(raw)[:64]
        with self._cost_lock:
            if s in self._session_labels:
                return s
            if len(self._session_labels) < self._session_label_cap:
                self._session_labels.add(s)
                return s
        return "~overflow"

    def _note_cost(self, tier: str, strategy: str, session: str,
                   tenant: str, device_ms: float, kv_ticks: float) -> None:
        """Fold one finished request's attributed cost into the bounded
        ledger (oldest key evicted past the cap — dict insertion order
        is the age order; a re-charged key keeps its slot)."""
        key = (tier, strategy, session, tenant)
        with self._cost_lock:
            entry = self._cost_ledger.get(key)
            if entry is None:
                while len(self._cost_ledger) >= self._cost_ledger_cap:
                    self._cost_ledger.pop(
                        next(iter(self._cost_ledger)))
                entry = self._cost_ledger[key] = {
                    "device_time_ms": 0.0, "kv_block_ticks": 0.0,
                    "requests": 0}
            entry["device_time_ms"] += device_ms
            entry["kv_block_ticks"] += kv_ticks
            entry["requests"] += 1

    def autoscaler_snapshot(self) -> Optional[Dict[str, Any]]:
        """The GET /stats ``autoscaler`` block: per armed tier, the
        bounds/windows, live membership, streak state, event counters,
        and the bounded decision ledger.  None when no tier arms the
        autoscaler (static configs keep their historical /stats shape)."""
        if not getattr(self, "autoscalers", None):
            return None
        return {name: scaler.snapshot()
                for name, scaler in self.autoscalers.items()}

    def cost_snapshot(self) -> List[Dict[str, Any]]:
        """The GET /stats ``cost`` block: attributed device time and KV
        block-ticks per (tier, strategy, session, tenant), most
        expensive first."""
        with self._cost_lock:
            rows = [
                {"tier": k[0], "strategy": k[1], "session": k[2],
                 "tenant": k[3],
                 "device_time_ms": round(v["device_time_ms"], 3),
                 "kv_block_ticks": round(v["kv_block_ticks"], 3),
                 "requests": int(v["requests"])}
                for k, v in self._cost_ledger.items()]
        rows.sort(key=lambda r: r["device_time_ms"], reverse=True)
        return rows

    def _live_engines(self):
        """(label, engine) of every live engine.  Advisory reads — never
        the lifecycle lock."""
        for name, tier in self.tiers.items():
            mgr = tier.server_manager
            subs = getattr(mgr, "live_engines", None)
            if callable(subs):
                # Replicated tier: one label PER REPLICA ("nano/r0",
                # "nano/r1", ...) so Perfetto shows the replicas' tick
                # timelines side by side.
                engines = [(f"{name}/{key}", eng) for key, eng in subs()]
            else:
                engines = [(name, getattr(mgr, "_engine", None))]
            yield from ((label, engine) for label, engine in engines
                        if engine is not None)

    def _live_profilers(self):
        """(label, TickProfiler) of every live engine that has one on;
        tiers without a profiler (remote, sequential, DLLM_PROFILE=0)
        contribute nothing."""
        for label, engine in self._live_engines():
            prof = getattr(engine, "profiler", None)
            if prof is not None and getattr(prof, "enabled", False):
                yield label, prof

    def step_programs(self, **select) -> Dict[str, Any]:
        """The GET /debug/programs body: per live batching engine, its
        decode tick and chunk programs (``select``: ``stage``,
        ``window_tokens``, ``ops``) with the named scope of each of their
        operations (``engine.step_programs``; the FIRST request about a
        program compiles it again, later ones read the engine's copy)."""
        return {"tiers": {label: engine.step_programs(**select)
                          for label, engine in self._live_engines()
                          if callable(getattr(engine, "step_programs",
                                              None))}}

    def profiler_trace(self, since: Optional[float] = None,
                       until: Optional[float] = None) -> Dict[str, Any]:
        """The GET /debug/trace body: every live engine's tick-phase
        ring + compile/host-sync events rendered as one Chrome-trace/
        Perfetto JSON document (obs/profiler.chrome_trace), cut to
        [since, until] wall seconds where given."""
        from ..obs import profiler as obs_profiler
        by_tier: Dict[str, Dict[str, Any]] = {}
        for label, prof in self._live_profilers():
            try:
                by_tier[label] = prof.snapshot()
            except Exception:
                pass
        return obs_profiler.chrome_trace(by_tier, since=since, until=until)

    def export_tick_totals(self) -> None:
        """Raise the counters that mirror the tick profilers' lifetime
        totals: ``dllm_tick_phase_ms_total`` and
        ``dllm_tick_phase_cpu_ms_total`` ``{tier,phase}`` (self wall and
        self CPU), ``dllm_sched_runqueue_wait_ms_total``, and the edge
        lanes' ``dllm_edge_awake_ms_total{clock}``,
        ``dllm_edge_wakeups_total``, ``dllm_edge_tokens_total`` and
        ``dllm_edge_wake_lag_ms``.  Called from the sampler's collect
        and from ``GET /metrics``, so a scrape reads the totals as of
        the scrape and nothing is added to the tick path or to a
        stream's consumer.  A counter never falls: a total below the
        last one seen under the same label (an engine rebuilt from 0)
        counts from there again."""
        m = self.obs.m
        with self._tick_totals_lock:
            for label, prof in self._live_profilers():
                try:
                    edge = prof.edge_totals()
                    runq = prof.runqueue_wait_ms()
                    rows = [(m.tick_phase_ms, (phase,), total) for
                            phase, total in prof.self_totals().items()]
                    rows += [(m.tick_phase_cpu_ms, (phase,), total) for
                             phase, total in prof.cpu_totals().items()]
                except Exception:
                    continue
                rows += [(m.edge_awake_ms, ("wall",), edge["wall_ms"]),
                         (m.edge_awake_ms, ("cpu",), edge["cpu_ms"]),
                         (m.edge_wakeups, (), edge["wakeups"]),
                         (m.edge_tokens, (), edge["tokens"])]
                if runq is not None:
                    rows.append((m.sched_runqueue_wait_ms, (), runq))
                for fam, rest, total in rows:
                    delta = self._unseen(total, fam.name, label, *rest)
                    if delta > 0:
                        fam.labels(label, *rest).inc(delta)
                # The wake-lag histogram, bucket growth by bucket growth
                # (the lanes bucket it themselves: no lock a slice).
                counts = [self._unseen(n, "edge_lag_bucket", label, ix)
                          for ix, n in enumerate(edge["lag_counts"])]
                lag_ms = self._unseen(edge["lag_ms"], "edge_lag_ms", label)
                if any(counts):
                    m.edge_wake_lag_ms.labels(label).merge(counts, lag_ms)

    def _unseen(self, total: float, *key: Any) -> float:
        """Growth of a lifetime total since ``export_tick_totals`` last
        saw it under ``key``; all of it after a fall.  Caller holds
        ``_tick_totals_lock``."""
        seen = self._tick_totals_seen.get(key, 0)
        self._tick_totals_seen[key] = total
        return total - seen if total >= seen else total

    def _obs_state_snapshot(self) -> Dict[str, Any]:
        """Cheap serving-state snapshot attached to flight-recorder
        entries: per-tier load counters + breaker states.  Deliberately
        NOT manager.health() — that takes the lifecycle lock, which a
        mid-compile engine can hold for minutes."""
        snap: Dict[str, Any] = {}
        try:
            tiers: Dict[str, Any] = {}
            for name, tier in self.tiers.items():
                fn = getattr(tier, "load_snapshot", None)
                if fn is not None:
                    tiers[name] = fn()
            snap["tiers"] = tiers
            if self.breaker is not None:
                snap["breaker"] = self.breaker.snapshot()
            snap["degraded_served"] = self.degraded_served
            # System TRAJECTORY, not just the point snapshot: the last
            # few seconds of the state timeline ride with every flight-
            # recorder entry (was the queue growing or draining when
            # this request failed?).
            timeline = self._timeline_tail(16)
            if timeline:
                snap["timeline"] = timeline
        except Exception:                 # snapshot must never kill a reply
            pass
        return snap

    def _finish_request(self, trace, which: Optional[str], ok: bool,
                        degraded: bool = False, raw: Any = None) -> None:
        """Close a request trace and derive its metrics + (for failed /
        degraded / slow requests) its flight-recorder entry.  Called
        exactly once per request, on every exit path of both pipelines."""
        trace.finish(ok=ok)
        m = self.obs.m
        strategy = trace.attrs.get("strategy") or "unknown"
        outcome = "degraded" if degraded else ("ok" if ok else "error")
        m.requests.labels(strategy, which or "none", outcome).inc()
        dur = trace.duration_ms
        if dur is not None:
            m.request_ms.labels(strategy).observe(dur)
        # Engine-true per-request timing rides in the raw dict (additive
        # keys, serving/tiers.py).  Cache hits skip the latency
        # histograms: a cached reply's raw carries the ORIGINAL
        # generation's timings, and its own TTFT is ~0 — both would
        # poison the engine-latency distributions.
        cache_hit = bool(trace.attrs.get("cache_hit"))
        ttft = tbt_p95 = None
        if not cache_hit:
            if isinstance(raw, dict):
                for key in ("ttft_ms", "total_ms", "gen_tokens"):
                    val = raw.get(key)
                    if val is not None:
                        trace.annotate(**{key: val})
            ttft = trace.ttft_ms()
            if ttft is not None:
                m.ttft_ms.labels(strategy).observe(ttft)
            tbt = trace.tbt_ms()
            if tbt is not None:
                m.tbt_ms.labels(strategy).observe(tbt)
            tbt_p95 = trace.tbt_p95_ms()
        # The engine's own split of TTFT, beside each other: waiting
        # for a slot (of which lane_wait_ms is the part spent behind a
        # busy prefill lane), then prefilling.
        for attr, fam in (("queue_wait_ms", m.queue_wait_ms),
                          ("prefill_wait_ms", m.prefill_wait_ms),
                          ("lane_wait_ms", m.prefill_lane_wait_ms)):
            val = trace.attrs.get(attr)
            if val is not None and which:
                fam.labels(which).observe(float(val))
        hold = trace.attrs.get("first_delta_hold_ms")
        if hold is not None:
            m.first_delta_hold_ms.labels(strategy).observe(float(hold))
        # SLO goodput feed — the ONLY sanctioned record_request site
        # (obs_discipline lint): this exit runs exactly once per request
        # on every path of both pipelines, so goodput counts requests,
        # never attempts.  Degraded service is not goodput even when the
        # stale-cache reply carried ok=True.
        tenant_raw = trace.attrs.get("tenant") or DEFAULT_TENANT
        tenant = self.obs.tenant_labels.label(tenant_raw)
        self.slo.record_request(strategy, which, ok=ok and not degraded,
                                ttft_ms=ttft, tbt_p95_ms=tbt_p95,
                                cache_hit=cache_hit, tenant=tenant)
        # A completed (admitted) request is the falling edge of this
        # tenant's over-quota incident, if one is open; a tenant-quota
        # rejection is not completion.
        if not (isinstance(raw, dict)
                and "tenant '" in str(raw.get("error", ""))):
            self._tenant_incident_edge(tenant_raw, rejected=False)
        # Per-request cost attribution (ISSUE 11): the batched engine
        # charged decode device time + KV block-ticks onto the trace;
        # this exactly-once exit aggregates them per (tier, strategy,
        # session) — the metric families quotas (ROADMAP 4) and
        # goodput-per-replica-second economics (ROADMAP 5) bill
        # against, plus the bounded /stats cost ledger.
        dev_ms = getattr(trace, "device_time_ms", 0.0)
        kv_ticks = getattr(trace, "kv_block_ticks", 0.0)
        if dev_ms or kv_ticks:
            session = self._session_label(trace.attrs.get("session"))
            m.device_time.labels(which or "none", strategy,
                                 session).inc(dev_ms)
            m.kv_block_ticks.labels(which or "none", strategy,
                                    session).inc(kv_ticks)
            m.tenant_device_time.labels(which or "none", tenant).inc(dev_ms)
            m.tenant_kv_block_ticks.labels(which or "none",
                                           tenant).inc(kv_ticks)
            self._note_cost(which or "none", strategy, session, tenant,
                            dev_ms, kv_ticks)
            # Post-paid quota billing (ISSUE 17): debit the serving
            # tier's per-tenant token bucket with the MEASURED device
            # time — quotas enforce observed cost, not declared cost.
            # No-op when the tier runs quotas-off (tenants is None).
            tier_client = self.tiers.get(which) if which else None
            tq = getattr(tier_client, "tenants", None)
            if tq is not None:
                try:
                    tq.debit(tenant_raw, dev_ms)
                except Exception:
                    pass
        reason = self.obs.recorder.classify(ok, degraded, dur)
        if reason is not None:
            m.flight_records.labels(reason).inc()
            self.obs.recorder.record(reason, trace,
                                     self._obs_state_snapshot())

    # -- helpers -----------------------------------------------------------

    def _apply_prefix_affinity(self, device: str, confidence: float,
                               method: str, reasoning: str, history
                               ) -> Tuple[str, str, str]:
        """Steer a LOW-confidence decision to the tier already holding
        this conversation's parked KV prefix (cache-locality-aware
        routing — beyond the reference, production only).

        Probes are non-destructive (PrefixCache.peek through
        engine.prefix_affinity), touch only ALREADY-RUNNING local
        engines (never starts one, never crosses hosts), and only
        override when the other tier's match beats the chosen tier's by
        at least ``prefix_affinity_min_tokens`` — a confident routing
        decision or a trivial prefix never flips.

        UPGRADE-ONLY: affinity may steer toward a STRONGER tier (later
        in the cluster's declaration order — the reference's nano<orin
        topology), never downgrade.  Locality must not cost capability:
        a complex follow-up whose early small-talk parked the
        conversation on nano still belongs on orin (measured: the
        symmetric rule dragged orin-labeled queries to nano and cost
        the semantic/hybrid cache-on legs ~0.17 accuracy; the reference
        resolves every such tie toward orin too — threshold fallback,
        heavy-context override)."""
        if (not self.enable_prefix_affinity
                or confidence >= self.prefix_affinity_min_confidence):
            return device, method, reasoning
        order = [t.name for t in self.cluster.tiers()]
        scores: Dict[str, int] = {}
        for name, tier in self.tiers.items():
            if (name not in order or device not in order
                    or order.index(name) <= order.index(device)):
                continue                 # upgrade-only: skip weaker tiers
            probe = self._tier_affinity_probe(tier)
            if callable(probe):
                try:
                    scores[name] = int(probe(history))
                except Exception:
                    scores[name] = 0
        if not scores:
            return device, method, reasoning
        # The chosen tier's own match sets the bar the upgrade must beat.
        own_probe = self._tier_affinity_probe(self.tiers[device])
        own = 0
        if callable(own_probe):
            try:
                own = int(own_probe(history))
            except Exception:
                own = 0
        best = max(scores, key=scores.get)
        if (best != device
                and scores[best] >= own + self.prefix_affinity_min_tokens):
            reasoning = (f"prefix affinity: {best} holds a "
                         f"{scores[best]}-token parked prefix of this "
                         f"conversation (decision was {device} at "
                         f"confidence {confidence:.2f}); {reasoning}")
            self.prefix_affinity_overrides += 1
            self.obs.m.cache_hits.labels("prefix_affinity").inc()
            obs_spans.event(current_trace(), "prefix_affinity_override",
                            to=best, match_tokens=scores[best])
            return best, f"{method}+prefix_affinity", reasoning
        return device, method, reasoning

    @staticmethod
    def _tier_affinity_probe(tier):
        """The tier's prefix-affinity probe: the ReplicaSetManager's
        best-across-replicas view for replicated tiers (a tier holds a
        prefix if ANY replica does), else the single engine's — never
        starts an engine either way."""
        mgr = tier.server_manager
        probe = getattr(mgr, "prefix_affinity", None)
        if callable(probe):
            return probe
        engine = getattr(mgr, "_engine", None)
        return getattr(engine, "prefix_affinity", None)

    @staticmethod
    def _extract_text(response: Any) -> Optional[str]:
        """Normalize any tier response shape to a plain string
        (src/router.py:73-102)."""
        if response is None:
            return None
        if isinstance(response, str):
            return response.strip() or None
        if isinstance(response, dict):
            for key in ("response", "content", "message"):
                val = response.get(key)
                if isinstance(val, str) and val.strip():
                    return val.strip()
                if isinstance(val, dict):
                    inner = val.get("content")
                    if isinstance(inner, str) and inner.strip():
                        return inner.strip()
            if "error" in response:
                parts = [str(response.get(k, "")).strip()
                         for k in ("error", "detail", "body")]
                combined = " ".join(p for p in parts if p)
                return combined[:300] if combined else None
        return None

    def _history_to_query_and_context(
        self, history: List[Dict[str, Any]]
    ) -> Tuple[str, Optional[str], str]:
        """Split history into (last user query, prior-turn context string,
        sha256[:16] hash of the last-k turns) — src/router.py:104-147."""
        if not history:
            return "", None, "nohist"

        last_user = None
        for i in range(len(history) - 1, -1, -1):
            m = history[i]
            if isinstance(m, dict) and m.get("role") == "user":
                last_user = i
                break

        if last_user is None:
            query, ctx_msgs = "", history
        else:
            query = (history[last_user].get("content") or "").strip()
            ctx_msgs = history[:last_user]

        lines = [
            f"{(m.get('role') or '').strip()}: {(m.get('content') or '').strip()}"
            for m in ctx_msgs
            if isinstance(m, dict) and (m.get("content") or "").strip()
        ]
        context = "\n".join(lines) if lines else None

        compact = "\n".join(
            f"{m.get('role', '')}:{(m.get('content') or '').strip()}"
            for m in ctx_msgs[-self.cache_last_k:]
            if isinstance(m, dict))
        ctx_hash = hashlib.sha256(compact.encode("utf-8")).hexdigest()[:16]
        return query, context, ctx_hash

    @staticmethod
    def _is_error(raw: Any) -> bool:
        # Delegates to the single error-shape schema (serving/errors.py)
        # that the `error-shape` lint checker enforces on every literal.
        return is_error_shape(raw)

    @staticmethod
    def _is_transient_error(raw: Any) -> bool:
        """Error shapes worth one quick same-tier retry (connection races,
        engine shut down mid-flight) — see _TRANSIENT_MARKERS."""
        if not (isinstance(raw, dict) and "error" in raw):
            return False
        msg = str(raw.get("error", "")).lower()
        return any(m in msg for m in _TRANSIENT_MARKERS)

    @staticmethod
    def _other(device: str) -> str:
        return "orin" if device == "nano" else "nano"

    def _tier_timeout_s(self, device: str) -> Optional[float]:
        """The tier's per-request wall budget (TierConfig.request_timeout_s
        locally, the read timeout for a remote tier); None = unbounded."""
        tier = self.tiers.get(device)
        cfg = getattr(tier, "tier", None)
        if cfg is not None and cfg.request_timeout_s:
            return float(cfg.request_timeout_s)
        read_timeout = getattr(tier, "read_timeout", None)
        return float(read_timeout) if read_timeout else None

    @staticmethod
    def _is_admission_rejection(raw: Any) -> bool:
        return (isinstance(raw, dict)
                and "admission rejected" in str(raw.get("error", "")))

    def _note_admission_rejection(self, raw: Any, which: str) -> None:
        """Admission-rejection metrics: every rejection counts, and the
        KV-pressure subset gets its own counter (the signal the pressure
        chaos leg and dashboards key on).  Tenant-quota rejections
        (ISSUE 17; reason names the tenant) additionally feed the
        per-tenant shed counter and the over-quota incident edge."""
        if not self._is_admission_rejection(raw):
            return
        self.obs.m.admission_rejected.labels(which).inc()
        err = str(raw.get("error", ""))
        if "KV demand" in err:
            self.obs.m.kv_admission_rejected.labels(which).inc()
        if "tenant '" in err:
            trace = current_trace()
            tenant = (trace.attrs.get("tenant")
                      if trace is not None else None) or DEFAULT_TENANT
            self.obs.m.tenant_rejected.labels(
                which, self.obs.tenant_labels.label(tenant)).inc()
            self._tenant_incident_edge(tenant, rejected=True,
                                       which=which, reason=err)

    def _tenant_incident_edge(self, tenant: str, rejected: bool,
                              which: Optional[str] = None,
                              reason: str = "") -> None:
        """Over-quota incident lifecycle (ISSUE 17): a tenant's FIRST
        quota rejection opens a flight-recorder incident naming it
        (rising edge — post-mortem survives a crash mid-shed, same
        contract as the SLO overload incidents); subsequent rejections
        only bump its count; the tenant's next COMPLETED request
        finalizes it.  At most ``_session_label_cap`` distinct tenants
        tracked — past that, rejections still count in metrics but mint
        no new incidents."""
        if rejected:
            with self._cost_lock:
                st = self._tenant_incidents.get(tenant)
                if st is not None:
                    st["rejections"] += 1
                    return
                if len(self._tenant_incidents) >= self._session_label_cap:
                    return
                st = {"entry": None, "rejections": 1}
                self._tenant_incidents[tenant] = st
            info = {"tenant": tenant, "tier": which or "none",
                    "first_reason": (reason or "")[:200],
                    "start_unix": round(time.time(), 3), "open": True}
            try:
                st["entry"] = self.obs.recorder.record_incident(
                    "tenant_overquota", info)
                self.obs.m.flight_records.labels("tenant_overquota").inc()
            except Exception:
                pass
            return
        with self._cost_lock:
            st = self._tenant_incidents.pop(tenant, None)
        if st is None:
            return
        entry = st.get("entry")
        if entry is not None:
            try:
                self.obs.recorder.update_incident(
                    entry, open=False, end_unix=round(time.time(), 3),
                    rejections_while_open=int(st["rejections"]))
            except Exception:
                pass

    # -- context-overflow policy (serving edge) ----------------------------

    def _apply_overflow_policy(self, device: str,
                               history: List[Dict[str, Any]]
                               ) -> Tuple[List[Dict[str, Any]],
                                          Optional[Dict[str, Any]], int]:
        """Per-tier policy for prompts exceeding ``max_seq_len -
        max_new_tokens`` (estimated with the router's token counter):
        ``reject`` fails fast with the reference error shape naming the
        policy; ``truncate_left`` (default) drops oldest turns until the
        estimate fits — the engine would silently keep the tail anyway
        (prepare_prompt), so this makes the choice explicit serving
        policy and surfaces it in the response.  The final (newest)
        message always survives.  Returns (history, error_raw | None,
        dropped_messages)."""
        tier = self.tiers.get(device)
        cfg = getattr(tier, "tier", None)
        if cfg is None or not isinstance(history, list):
            return history, None, 0
        try:
            limit = max(1, cfg.model().max_seq_len - cfg.max_new_tokens)
        except Exception:
            return history, None, 0
        est = self.token_counter.get_context_size(history)
        if est <= limit:
            return history, None, 0
        policy = getattr(cfg, "overflow_policy", "truncate_left")
        if policy == "reject":
            self.obs.m.overflow.labels(device, "rejected").inc()
            obs_spans.event(current_trace(), "overflow_rejected",
                            tier=device, est_tokens=est, limit=limit)
            logger.warning("%s: prompt ~%d tokens over the %d-token "
                           "context budget — overflow_policy=reject",
                           device, est, limit)
            return history, {"error": (
                f"Request failed: prompt of ~{est} tokens exceeds "
                f"{device}'s context budget of {limit} tokens "
                f"(max_seq_len - decode budget; "
                f"overflow_policy=reject)")}, 0
        trimmed = list(history)
        dropped = 0
        while len(trimmed) > 1 and est > limit:
            dropped += 1
            est -= self.token_counter.count_tokens(trimmed.pop(0))
        self.obs.m.overflow.labels(device, "truncated").inc()
        obs_spans.event(current_trace(), "overflow_truncated",
                        tier=device, dropped_messages=dropped,
                        est_tokens=est, limit=limit)
        logger.info("%s: dropped %d oldest turn(s) to fit the %d-token "
                    "context budget (overflow_policy=truncate_left)",
                    device, dropped, limit)
        return trimmed, None, dropped

    def _breaker_record(self, device: str, ok: bool,
                        raw: Any = None) -> None:
        """Feed a dispatch outcome to the breaker.  Admission rejections
        are NEITHER success nor failure: they are healthy backpressure
        (the queue-aware perf penalty's job), and counting them would
        open the circuit on a tier that is merely at capacity — a burst
        could then cascade both tiers into degraded fail-fast while both
        engines are healthy and draining."""
        if self.breaker is None:
            return
        if not ok and self._is_admission_rejection(raw):
            # Still repay a half-open canary permit: the rejection proves
            # the engine is up and draining — holding the permit would
            # shed the tier for another whole cooldown.
            self.breaker.release_probe(device)
            return
        self.breaker.record(device, ok)

    def _breaker_record_stream_setup(self, device: str, handle: Any) -> None:
        """Breaker feedback for a stream SETUP result: only FAILURES
        (error dicts, minus admission rejections) count here.  A
        successful setup proves one primed token, nothing more — a tier
        that wedges MID-decode (the round-5 mode) passes setup every
        time, and recording that as success would reset the failure
        streak each request and keep the circuit closed forever on a
        streaming-only workload.  ALL success verdicts come from stream
        completion (``on_done``)."""
        if self.breaker is None:
            return
        if self._is_error(handle):
            self._breaker_record(device, False, handle)

    def _run_device(self, device: str,
                    history: List[Dict[str, Any]]) -> Tuple[Any, str, float]:
        tier = self.tiers.get(device, self.nano)
        logger.info("Processing query on %s", tier.name)
        t0 = time.perf_counter()
        with obs_spans.span(current_trace(), "dispatch", tier=tier.name):
            raw = tier.process(history)
        self._note_admission_rejection(raw, tier.name)
        return raw, tier.name, (time.perf_counter() - t0) * 1000.0

    def _run_device_retrying(self, device: str, history: List[Dict[str, Any]],
                             deadline: Optional[float] = None
                             ) -> Tuple[Any, str, float]:
        """``_run_device`` plus bounded retry with jittered exponential
        backoff for TRANSIENT error shapes.  ``deadline`` (monotonic) is
        the retry layer's wall budget — the dispatching tier's
        request_timeout_s from dispatch start: no retry STARTS past it
        (a timed-out call has no retry budget left by construction).
        Each attempt is still individually capped by the tier's own
        timeout, so the theoretical worst case is budget + one per-call
        cap — reachable only by a transient failure surfacing at the
        budget's edge; in practice the retried shapes (connection
        refused/reset) fail in milliseconds."""
        raw, which, lat_ms = self._run_device(device, history)
        for attempt in range(self.retry_attempts):
            if not self._is_transient_error(raw):
                break
            backoff = (self.retry_backoff_s * (2 ** attempt)
                       * (0.5 + random.random()))
            if (deadline is not None
                    and time.monotonic() + backoff >= deadline):
                logger.warning("%s transient error but no retry budget "
                               "left — giving up the retry", which)
                break
            logger.warning("%s transient error (%.80s) — retry %d/%d after "
                           "%.0fms", which, raw.get("error", ""),
                           attempt + 1, self.retry_attempts, backoff * 1000)
            self.obs.m.retries.labels(which).inc()
            obs_spans.event(current_trace(), "retry", tier=which,
                            attempt=attempt + 1)
            time.sleep(backoff)
            raw2, _, lat2 = self._run_device(device, history)
            lat_ms += lat2
            raw = raw2
        return raw, which, lat_ms

    # -- response cache (src/router.py:179-193) ----------------------------

    def _response_cache_key(self, ctx_hash: str, query: str) -> str:
        # Deliberately context-independent (reference intent, router.py:57-59)
        return f"{self.query_router.strategy}|{query.lower().strip()}"

    def _degraded_response(self, query: str, ctx_hash: str, method: str,
                           confidence: float, overhead_ms: float,
                           device: str) -> Tuple[Dict[str, Any], int, str]:
        """Both tiers' circuits are open: serve a response-cache hit if
        one exists (stale beats dead), else fail FAST with the reference
        error shape plus a retry-after hint — never dispatch into a
        known-dead cluster and burn a serving thread on a timeout."""
        cached = self._response_store.get(
            self._response_cache_key(ctx_hash, query))
        # Skip error-shaped entries: the store keeps every reply
        # (reference behavior), and re-serving a cached ERROR as an
        # ok=True "degraded hit" would report a failure as an answer.
        if cached is not None and self._is_error(cached.get("raw")):
            cached = None
        if cached is not None:
            text = cached.get("text", "")
            which = cached.get("device", device)
            tokens = self.token_counter.count_tokens(
                {"role": "assistant", "content": text})
            self.degraded_served += 1
            self.obs.m.degraded.inc()
            self.obs.m.cache_hits.labels("response_degraded").inc()
            obs_spans.annotate(current_trace(), degraded=True,
                               cache_hit="response_degraded")
            return {
                "response": text,
                "raw": cached.get("raw"),
                "cache_hit": True,
                "degraded": True,
                "routing_method": "response_cache_degraded",
                "routing_confidence": 1.0,
                "routing_reasoning": ("all tiers' circuits open -> stale "
                                      f"response-cache hit ({which})"),
                "routing_overhead_ms": round(overhead_ms, 2),
                "ok": True,
            }, tokens, which
        retry_after = (self.breaker.retry_after_s()
                       if self.breaker is not None else 0.0)
        raw = {"error": ("Request failed: all tiers unavailable (circuit "
                         f"open); retry in {retry_after:.1f}s")}
        text = self._extract_text(raw) or "No response available"
        tokens = self.token_counter.count_tokens(
            {"role": "assistant", "content": text})
        self.degraded_served += 1
        self.obs.m.degraded.inc()
        obs_spans.event(current_trace(), "degraded_fail_fast",
                        retry_after_s=round(retry_after, 2))
        obs_spans.annotate(current_trace(), degraded=True)
        logger.warning("degraded fail-fast: all circuits open "
                       "(retry_after=%.1fs)", retry_after)
        return {
            "response": text,
            "raw": raw,
            "cache_hit": False,
            "degraded": True,
            "retry_after_s": round(retry_after, 2),
            "benchmark_mode": self.benchmark_mode,
            "routing_method": f"{method}+breaker_degraded",
            "routing_confidence": round(confidence, 4),
            "routing_reasoning": ("all tiers' circuits open; shedding "
                                  "without dispatch"),
            "routing_overhead_ms": round(overhead_ms, 2),
            "ok": False,
        }, tokens, device

    # -- main pipeline -----------------------------------------------------

    def _feed_perf_load(self) -> None:
        """Queue-aware routing input: push each tier's live load
        (admission queue depth + batch slot occupancy) into the active
        strategy before it decides.  Cheap in-memory counters; skipped
        entirely unless the strategy consumes them (perf only)."""
        if (self.breaker is not None
                and hasattr(getattr(self.query_router, "router", None),
                            "update_breaker")):
            # Breaker state reaches the strategies too (perf scores an
            # OPEN tier a whole fail_penalty), so shedding starts at the
            # DECISION, before the Router's dispatch-time veto.  Gated on
            # the ACTIVE strategy consuming it — same pattern as
            # wants_load: no per-request breaker lock/snapshot for the
            # strategies that ignore the feed.
            for name, st in self.breaker.snapshot().items():
                try:
                    self.query_router.update_breaker(
                        name, st["state"] == "open")
                except Exception:
                    pass
        if not getattr(self.query_router, "wants_load", False):
            return
        for name, tier in self.tiers.items():
            snap_fn = getattr(tier, "load_snapshot", None)
            if snap_fn is None:
                continue                     # remote tiers: no local load
            try:
                self.query_router.update_load(name, **snap_fn())
            except Exception:
                pass

    def _decide(self, query: str, context: str, ctx_hash: str,
                history: List[Dict[str, Any]]):
        """The routing-decision stage shared by the sync and streaming
        pipelines: QueryRouter decision with the reference's ctx-size
        fallback on engine failure (src/router.py:258-270).  Returns
        (device, method, confidence, reasoning, cache_hit, overhead_ms)."""
        t0 = time.perf_counter()
        with obs_spans.span(current_trace(), "route") as route_sp:
            self._feed_perf_load()
            device = "nano"
            method, confidence, reasoning = "unknown", 0.0, ""
            cache_hit = False
            try:
                decision = self.query_router.route_query(
                    query=query, context=context, context_key=ctx_hash)
                device = decision.device
                method = decision.method
                confidence = float(decision.confidence)
                reasoning = decision.reasoning
                cache_hit = bool(decision.cache_hit)
                logger.info("[%s] routing: %s | method=%s conf=%.3f",
                            "BENCH" if self.benchmark_mode else "PROD",
                            device.upper(), method, confidence)
            except Exception as exc:
                ctx_size = self.token_counter.get_context_size(history)
                device = ("orin" if ctx_size > self.threshold_fallback
                          else "nano")
                method = "fallback_ctx_size"
                confidence = 0.2
                reasoning = (f"router failed: {exc}; ctx_size={ctx_size}, "
                             f"threshold_fallback={self.threshold_fallback}")
                logger.warning("routing failed (%s); ctx fallback -> %s",
                               exc, device)
            route_sp.annotate(device=device, method=method,
                              confidence=round(confidence, 4))
            if cache_hit:
                self.obs.m.cache_hits.labels("routing").inc()
        overhead_ms = (time.perf_counter() - t0) * 1000.0
        return device, method, confidence, reasoning, cache_hit, overhead_ms

    def route_query(self, history: List[Dict[str, Any]],
                    session_id: Optional[str] = None,
                    tenant_id: Optional[str] = None
                    ) -> Tuple[Dict[str, Any], int, str]:
        """Instrumented entry: creates the request's span tree (obs/),
        binds it for this thread (tiers/engines pick it up via
        ``current_trace``), runs the pipeline, then derives the
        request's metrics and — when failed/degraded/slow — its flight-
        recorder entry.  The pipeline itself is ``_route_query_inner``;
        the reference contract (return shape, error semantics) is
        untouched.  ``session_id`` (optional, additive — the serving
        edge passes its /chat session) keys the per-session cost
        attribution; None aggregates under '-'.  ``tenant_id``
        (ISSUE 17; validated at the serving edge) rides the trace into
        the tier quota layer and keys per-tenant billing; None bills to
        the shared default tenant."""
        self._ensure_sampler()
        trace = self.obs.trace(strategy=self.query_router.strategy)
        if session_id:
            trace.annotate(session=str(session_id))
        if tenant_id:
            trace.annotate(tenant=str(tenant_id))
        with use_trace(trace):
            try:
                response, tokens, which = self._route_query_inner(
                    trace, history)
            except BaseException as exc:
                trace.annotate(error=f"{type(exc).__name__}: {exc}"[:200])
                self._finish_request(trace, None, ok=False)
                raise
        self._finish_request(trace, which,
                             ok=bool(response.get("ok", True)),
                             degraded=bool(response.get("degraded")),
                             raw=response.get("raw"))
        return response, tokens, which

    def _route_query_inner(self, trace, history: List[Dict[str, Any]]
                           ) -> Tuple[Dict[str, Any], int, str]:
        query, context, ctx_hash = self._history_to_query_and_context(history)

        # 0) response cache
        if self.enable_response_cache:
            with trace.span("cache_lookup"):
                cached = self._response_store.get(
                    self._response_cache_key(ctx_hash, query))
            if cached is not None:
                text = cached.get("text", "")
                which = cached.get("device", "nano")
                tokens = self.token_counter.count_tokens(
                    {"role": "assistant", "content": text})
                self.obs.m.cache_hits.labels("response").inc()
                trace.annotate(cache_hit="response")
                return {
                    "response": text,
                    "raw": cached.get("raw"),
                    "cache_hit": True,
                    "routing_method": "response_cache",
                    "routing_confidence": 1.0,
                    "routing_reasoning": f"response cache hit -> {which}",
                    "routing_overhead_ms": 0.0,
                    "ok": True,
                }, tokens, which

        # 1) routing decision
        (device, method, confidence, reasoning,
         cache_hit, overhead_ms) = self._decide(query, context, ctx_hash,
                                                history)
        device, method, reasoning = self._apply_prefix_affinity(
            device, confidence, method, reasoning, history)

        # 1.6) circuit-breaker veto: an OPEN tier sheds traffic BEFORE
        # dispatch (before its admission queue even sees the request);
        # both tiers open → the degraded path (cache hit or fast fail
        # with a retry-after hint) instead of a doomed dispatch.
        if self.breaker is not None and not self.breaker.allow(device):
            other = self._other(device)
            if device in self.tiers and self.breaker.allow(other):
                reasoning = (f"circuit open on {device} -> rerouted to "
                             f"{other}; {reasoning}")
                method = f"{method}+breaker"
                trace.event("breaker_veto", vetoed=device, to=other)
                device = other
            else:
                return self._degraded_response(query, ctx_hash, method,
                                               confidence, overhead_ms,
                                               device)

        # 1.8) context-overflow policy for the dispatching tier: an over-
        # budget prompt either fails fast here (policy "reject") or loses
        # its oldest turns ("truncate_left"), with the choice surfaced.
        history, overflow_err, overflow_dropped = \
            self._apply_overflow_policy(device, history)
        if overflow_err is not None:
            text = self._extract_text(overflow_err) or "No response available"
            tokens = self.token_counter.count_tokens(
                {"role": "assistant", "content": text})
            return {
                "response": text,
                "raw": overflow_err,
                "cache_hit": False,
                "benchmark_mode": self.benchmark_mode,
                "routing_overhead_ms": round(overhead_ms, 2),
                "routing_method": f"{method}+overflow_reject",
                "routing_confidence": round(confidence, 4),
                "routing_reasoning": (f"prompt exceeds {device}'s context "
                                      f"budget (overflow_policy=reject); "
                                      f"{reasoning}"),
                "ok": False,
            }, tokens, device

        # 2) inference + bounded transient retry + failover.  The retry
        # layer is budgeted against the primary tier's request_timeout_s
        # from dispatch start (retries never extend the reference cap).
        timeout_s = self._tier_timeout_s(device)
        deadline = (time.monotonic() + timeout_s
                    if timeout_s is not None else None)
        raw, which, lat_ms = self._run_device_retrying(device, history,
                                                       deadline)
        self._breaker_record(which, not self._is_error(raw), raw)
        if self.enable_failover and self._is_error(raw):
            other = self._other(which)
            # Record the PRIMARY's failure before switching: the
            # reference feeds perf only for the device that ultimately
            # served (router.py:292-295), so failover masked every
            # failure from the perf strategy — yet its fail_penalty
            # exists precisely to steer traffic off flaky devices.
            # Divergence documented in PARITY.md; especially load-bearing
            # for request timeouts (a wedged tier must lose traffic).
            try:
                self.query_router.update_perf(which, lat_ms, 0, ok=False)
            except Exception:
                pass
            # Failover keeps the reference's one-shot semantics — it
            # fires even after a full wall timeout (a wedged tier's
            # request MUST still reach the survivor; that is the round-5
            # scenario this layer exists for).  The deadline bounds only
            # the RETRY layer: the failover attempt runs retry-free when
            # the budget is spent.  Repeated timeout+failover cost is the
            # BREAKER's job — after breaker_failures of these, the wedged
            # tier sheds pre-dispatch and nobody pays the timeout again.
            # Only an open circuit on the survivor suppresses failover.
            if self.breaker is None or self.breaker.allow(other):
                logger.warning("%s failed — failing over to %s", which, other)
                self.obs.m.failovers.labels(which, "sync").inc()
                trace.event("failover", failed=which, to=other)
                raw2, which2, lat2 = self._run_device_retrying(
                    other, history, deadline)
                self._breaker_record(which2, not self._is_error(raw2), raw2)
                if not self._is_error(raw2):
                    raw, which, lat_ms = raw2, which2, lat2
            else:
                logger.warning("%s failed and %s's circuit is open — "
                               "no failover target", which, other)

        # 3) normalize + count
        text = self._extract_text(raw) or "No response available"
        tokens = self.token_counter.count_tokens(
            {"role": "assistant", "content": text})
        ok = not self._is_error(raw)

        # 4) perf feedback
        try:
            self.query_router.update_perf(which, lat_ms, tokens, ok=ok)
        except Exception:
            pass

        # 5) response-cache store
        if self.enable_response_cache:
            self._response_store[self._response_cache_key(ctx_hash, query)] = {
                "text": text,
                "raw": raw,
                "device": which,
                "routing_confidence": round(confidence, 4),
            }

        out = {
            "response": text,
            "raw": raw,
            "cache_hit": False,
            "benchmark_mode": self.benchmark_mode,
            "routing_overhead_ms": round(overhead_ms, 2),
            "routing_method": method,
            "routing_confidence": round(confidence, 4),
            "routing_reasoning": reasoning,
            "ok": ok,
        }
        if overflow_dropped:
            # Surface the truncate_left choice (additive keys, like the
            # per-request timing fields).
            out["overflow_truncated"] = True
            out["overflow_dropped_messages"] = overflow_dropped
        return out, tokens, which

    def route_query_stream(self, history: List[Dict[str, Any]],
                           session_id: Optional[str] = None,
                           tenant_id: Optional[str] = None
                           ) -> "RoutedStream":
        """Streaming twin of ``route_query``: same decision stage
        (``_decide`` incl. the ctx-size fallback), the same circuit-
        breaker veto, one-shot tier failover at stream SETUP, plus
        MID-STREAM failover — a stream whose decode loop dies after the
        first token is re-issued on the surviving tier with the already-
        emitted prefix replayed silently (RoutedStream) — and the same
        perf feedback, fired when the stream completes.  The response
        cache does not participate: a streamed reply is consumed as it
        is produced.  Raises RuntimeError if no tier can start a stream
        (message carries a retry-after hint when every circuit is
        open)."""
        self._ensure_sampler()
        trace = self.obs.trace(strategy=self.query_router.strategy,
                               stream=True)
        if session_id:
            trace.annotate(session=str(session_id))
        if tenant_id:
            trace.annotate(tenant=str(tenant_id))
        with use_trace(trace):
            try:
                return self._route_stream_inner(trace, history)
            except BaseException as exc:
                trace.annotate(error=f"{type(exc).__name__}: {exc}"[:200])
                self._finish_request(trace, None, ok=False,
                                     degraded=bool(
                                         trace.attrs.get("degraded")))
                raise

    def _route_stream_inner(self, trace,
                            history: List[Dict[str, Any]]) -> "RoutedStream":
        query, context, ctx_hash = self._history_to_query_and_context(history)
        (device, method, confidence, reasoning,
         cache_hit, overhead_ms) = self._decide(query, context, ctx_hash,
                                                history)
        device, method, reasoning = self._apply_prefix_affinity(
            device, confidence, method, reasoning, history)

        # Circuit-breaker veto, mirroring the sync path: shed an open
        # tier pre-dispatch; both open → fail fast with a retry hint.
        if self.breaker is not None and not self.breaker.allow(device):
            other = self._other(device)
            if self.breaker.allow(other):
                reasoning = (f"circuit open on {device} -> rerouted to "
                             f"{other}; {reasoning}")
                method = f"{method}+breaker"
                trace.event("breaker_veto", vetoed=device, to=other)
                device = other
            else:
                self.degraded_served += 1
                self.obs.m.degraded.inc()
                trace.annotate(degraded=True)
                raise RuntimeError(
                    "Request failed: all tiers unavailable (circuit "
                    f"open); retry in {self.breaker.retry_after_s():.1f}s")

        # Context-overflow policy, mirroring the sync path: reject raises
        # (the SSE layer splices the error tail), truncate_left trims and
        # flags the meta.
        history, overflow_err, overflow_dropped = \
            self._apply_overflow_policy(device, history)
        if overflow_err is not None:
            raise RuntimeError(overflow_err["error"])

        t0 = time.perf_counter()
        tier = self.tiers.get(device, self.nano)
        # Stream setup primes the first token (prefill runs inside), so
        # this span IS the stream's TTFT-critical section.
        with trace.span("stream_setup", tier=tier.name):
            handle = tier.process_stream(history)
        which = tier.name
        self._note_admission_rejection(handle, which)
        self._breaker_record_stream_setup(which, handle)
        if self._is_error(handle) and self.enable_failover:
            other = self._other(which)
            logger.warning("%s stream setup failed — failing over to %s",
                           which, other)
            # Same as the sync path: the primary's failure must reach
            # the perf strategy even though failover will serve.
            try:
                self.query_router.update_perf(
                    which, (time.perf_counter() - t0) * 1000.0, 0, ok=False)
            except Exception:
                pass
            if self.breaker is None or self.breaker.allow(other):
                self.obs.m.failovers.labels(which, "stream_setup").inc()
                trace.event("failover", failed=which, to=other,
                            kind="stream_setup")
                with trace.span("stream_setup", tier=other):
                    alt = self.tiers[other].process_stream(history)
                self._note_admission_rejection(alt, other)
                self._breaker_record_stream_setup(other, alt)
                if not self._is_error(alt):
                    handle, which = alt, other
        if self._is_error(handle):
            raise RuntimeError(handle.get("error", "stream setup failed"))

        # Shared mutable view of the live (handle, device): mid-stream
        # failover swaps both, and the completion callback must attribute
        # the final result to the tier that ACTUALLY finished the stream.
        state: Dict[str, Any] = {"handle": handle, "device": which}

        def on_done(ok: bool) -> None:
            # The stream's COMPLETION is the breaker's verdict for the
            # serving tier (setup only primes one token — see
            # _breaker_record_stream_setup): a half-open canary closes
            # the circuit only by finishing its stream.
            self._breaker_record(state["device"], ok)
            result = getattr(state["handle"], "result", None)
            # Engine-true generation time, NOT wall time to exhaustion: a
            # slow SSE consumer would otherwise poison the perf strategy's
            # latency window for a healthy tier.
            if result is not None and result.total_ms > 0:
                lat_ms = result.total_ms
            else:
                lat_ms = (time.perf_counter() - t0) * 1000.0
            tokens = result.gen_tokens if result else 0
            try:
                self.query_router.update_perf(state["device"], lat_ms,
                                              tokens, ok=ok)
            except Exception:
                pass
            # Trace completion: engine-true timings preferred (token-
            # timeline stamps are the fallback for engines that report
            # no GenerationResult).  Fires exactly once via _fire.
            if result is not None:
                trace.annotate(ttft_ms=result.ttft_ms,
                               total_ms=result.total_ms,
                               gen_tokens=result.gen_tokens)
            self._finish_request(trace, state["device"], ok=ok)

        def on_first_delta() -> None:
            # The edge's hold-back, measured inside: the first token the
            # engine generated (token timeline) to the first delta this
            # stream hands to the SSE layer — the turn clipper's held
            # characters.  Observed at the exactly-once exit.
            times = trace.token_times
            if times:
                trace.annotate(first_delta_hold_ms=round(
                    (time.perf_counter() - times[0]) * 1000.0, 3))

        def resume_mid_stream(emitted_chars: int, exc: BaseException):
            """Mid-stream failover: the live stream died after emitting
            ``emitted_chars`` chars.  Re-issue the SAME request on the
            surviving tier and return an iterator that silently replays
            (skips) the already-delivered prefix, or None when no tier
            can take over (the caller then surfaces the original
            failure).  The replacement tier re-generates from scratch —
            its first ``emitted_chars`` chars are dropped, so the client
            sees one seamless stream (prefix replay; the spliced suffix
            may of course diverge in wording from what the dead tier
            WOULD have said — it is a different model)."""
            if not self.enable_failover:
                return None
            dying = state["device"]
            other = self._other(dying)
            logger.warning("%s stream died mid-decode after %d chars (%s) "
                           "— re-issuing on %s", dying, emitted_chars, exc,
                           other)
            # On every None return below, on_done(False) fires for the
            # still-current state["device"] (the dying tier) — so the
            # dying tier's breaker/perf failure is recorded HERE only on
            # the success path, where on_done will credit the SURVIVOR
            # instead.  Recording in both places would double-count one
            # stream death and trip the breaker at half its threshold.
            if self.breaker is not None and not self.breaker.allow(other):
                return None
            # Counted at the ATTEMPT, like the sync and stream_setup
            # kinds — a takeover whose survivor also fails must not be
            # invisible in the failover rate.
            self.obs.m.failovers.labels(dying, "mid_stream").inc()
            trace.event("mid_stream_failover", failed=dying, to=other,
                        replayed_chars=emitted_chars)
            # The resume hook runs on the CONSUMER's thread (SSE drain),
            # outside the request's original context — re-bind the trace
            # so the replacement setup's spans land in the same tree.
            with use_trace(trace), \
                    trace.span("stream_setup", tier=other, resume=True):
                alt = self.tiers[other].process_stream(history)
            self._breaker_record_stream_setup(other, alt)
            if self._is_error(alt):
                logger.warning("mid-stream failover target %s also failed "
                               "(%s)", other, alt.get("error"))
                return None
            self._breaker_record(dying, False)
            try:
                self.query_router.update_perf(
                    dying, (time.perf_counter() - t0) * 1000.0, 0, ok=False)
            except Exception:
                pass
            state["handle"], state["device"] = alt, other

            def replayed():
                skip = emitted_chars
                for delta in alt:
                    if skip > 0:
                        if len(delta) <= skip:
                            skip -= len(delta)
                            continue
                        delta = delta[skip:]
                        skip = 0
                    yield delta

            return replayed()

        meta = {
            "device": which,
            "method": method,
            "confidence": round(confidence, 4),
            "reasoning": reasoning,
            # Same meaning as /chat's cache_hit (response cache) — streams
            # never serve from it, so always False; the routing-decision
            # cache hit is its own field (it also shows as "*_cached" in
            # method, matching the sync path's convention).
            "cache_hit": False,
            "routing_cache_hit": cache_hit,
            "routing_overhead_ms": round(overhead_ms, 2),
        }
        if overflow_dropped:
            meta["overflow_truncated"] = True
            meta["overflow_dropped_messages"] = overflow_dropped
        return RoutedStream(state, meta, on_done,
                            resume=resume_mid_stream,
                            on_first_delta=on_first_delta)


class RoutedStream:
    """A routed token stream: iterate for text deltas; ``.result`` holds
    the GenerationResult once exhausted.  Fires the router's perf-feedback
    callback exactly once, whether the stream completes, errors, or is
    abandoned mid-iteration (client disconnect).

    ``resume`` is the Router's mid-stream failover hook: when the LIVE
    stream raises between deltas (decode-loop death after the first
    token — setup-time failover can no longer help), it is called once
    with the number of chars already delivered; a non-None return is an
    iterator over the surviving tier's stream with that prefix already
    skipped (prefix replay), and iteration continues seamlessly.  A None
    return (failover disabled, no surviving tier, its circuit open)
    surfaces the original failure — the SSE layer splices the
    error-shaped tail event."""

    def __init__(self, state: Dict[str, Any], meta: Dict[str, Any],
                 on_done, resume=None, on_first_delta=None):
        self._state = state
        self.meta = meta
        self._on_done = on_done
        self._resume = resume
        self._on_first_delta = on_first_delta
        self._resumed = False
        self._fired = False

    @property
    def device(self) -> str:
        """The tier currently (or finally) serving this stream — updated
        if mid-stream failover switched tiers."""
        return self._state["device"]

    def _fire(self, ok: bool) -> None:
        if not self._fired:
            self._fired = True
            self._on_done(ok)

    def __iter__(self):
        emitted_chars = 0
        it = iter(self._state["handle"])
        while True:
            try:
                delta = next(it)
            except StopIteration:
                break
            except BaseException as exc:   # producer (engine/stream) death
                if self._resume is not None and not self._resumed:
                    self._resumed = True   # one-shot, like setup failover
                    alt = None
                    try:
                        alt = self._resume(emitted_chars, exc)
                    except Exception:
                        logger.exception("mid-stream failover hook failed")
                    if alt is not None:
                        it = alt
                        continue
                self._fire(False)
                raise
            if emitted_chars == 0 and self._on_first_delta is not None:
                self._on_first_delta()
                self._on_first_delta = None
            try:
                yield delta
            except GeneratorExit:
                # Consumer abandoned the stream (client disconnect) — the
                # TIER was healthy as far as it was consumed; an ok=False
                # sample here would let disconnecting clients poison the
                # perf strategy against a healthy tier.
                self._fire(True)
                raise
            emitted_chars += len(delta)
        self._fire(True)

    @property
    def result(self):
        return self._state["handle"].result
