"""Replicated tiers — N data-parallel engine replicas behind one tier.

Until ISSUE 12 a tier was exactly ONE engine, so aggregate throughput was
capped at one engine's knee and "scale out" meant an architecture change.
``TierConfig.replicas > 1`` makes the tier own N full ``EngineManager``
replicas — the TPU-serving data-parallel shape (per-replica batching over
a mesh axis; the Gemma-on-TPU comparison in PAPERS.md): when the tier's
submesh has enough devices each replica gets its own device slice
(``replicas × tp`` chips, the ``P('batch')`` data-parallel carve), and on
a single-device/CPU box the replicas are process-local engines sharing
the device.  Every replica keeps the WHOLE single-engine machinery it
had before — bounded admission queue + EWMA wait predictor (PR 1),
watchdog (PR 2), drain (PR 5), chunked prefill (PR 9), shared-prefix KV
(PR 10), tick profiler (PR 11) — because each replica IS a TierClient
over an EngineManager, just not the only one.

Dispatch picks a replica by a two-level policy:

1. **Prefix affinity** (``TierConfig.replica_affinity``): the request is
   tokenized ONCE and every live replica's parked-prefix cache is peeked
   with the same ids — the identical ``select_reuse``/longest-match the
   engines reuse blocks by (engine/prefix_cache.py), so the host-side
   "which replica holds this prefix" map is exactly the caches
   themselves, never a second bookkeeping structure that could drift.
   A match of at least ``replica_affinity_min_tokens`` binds the request
   to that replica — a session (or a same-system-prompt sibling) lands
   where its blocks are parked, so the PR 10 dedup/warm-TTFT win
   survives going multi-replica instead of being diluted N ways.
2. **Least-loaded** otherwise: smallest predicted queue wait
   (queue_depth / slots × EWMA service time — PR 1's admission
   predictor), ties broken by in-flight count then round-robin.  An
   affine replica whose predicted wait exceeds the least-loaded's by
   more than ``replica_affinity_override_s`` is OVERRIDDEN — cache
   locality must not starve the other replicas behind one hot queue.

Each replica has its own breaker sub-gate (serving/breaker.py, keyed
``r<rid>``, thresholds from the cluster's breaker config): dispatch
skips open replicas, stream/sync verdicts feed back per replica, and
admission rejections stay breaker-neutral (healthy backpressure — the
PR 2 rule).  Tier-level ``health()`` / ``kv_stats()`` / ``slot_stats()``
aggregate across replicas with a per-replica breakdown, and the
HealthMonitor probes/restarts replicas INDIVIDUALLY — one wedged
replica degrades capacity (``healthy_replicas``/``replica_count``)
instead of the tier.

**Dynamic membership (ISSUE 18).**  Membership is a LIST OF MEMBER
RECORDS shared between the client and its ReplicaSetManager, each
record carrying a monotonic replica id (``rid``) minted at build time
and NEVER reused — engine-side tier names (``nano/r2``), per-replica
metric labels, and breaker keys are baked at construction, so removal
must not shift surviving replicas' identities the way positional
indices would.  ``scale_to(n)`` is the actuation verb (the autoscaler's
— serving/autoscaler.py — and the operator's): scale-up builds each new
replica OFF-membership, warms it fully against the process XLA compile
cache replica 0 populated (new replicas compile nothing beyond their
own per-engine one-decode-program), and only then publishes it —
deferred go-live, dispatch never sees a cold replica; scale-down picks
the least-affine replica, removes it from membership FIRST (no new
dispatch), waits out its in-flight work, DEMOTES its refcount-1 parked
prefixes through the PR 13 host spill tier and hands the resident
entries to a survivor's store (scale-down costs warm TTFT, never
correctness), then drains and stops it.  All dispatch/probe/aggregate
paths iterate SNAPSHOTS (``list(members)`` — atomic under the GIL) so
they tolerate membership changes mid-flight.

``replicas = 1`` without ``autoscale`` never builds any of this:
build_tiers keeps the plain TierClient/EngineManager path,
byte-identical to pre-replica behavior.
"""

from __future__ import annotations

import dataclasses
import logging
import random
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..config import ClusterConfig, TierConfig
from ..config_registry import env_str
from ..engine.manager import EngineManager
from ..obs import get_observability
from ..obs import spans as obs_spans
from ..obs.spans import current_trace
from ..utils.faults import FaultInjector
from .breaker import CircuitBreaker, OPEN
from .errors import error_dict, is_error_shape
from .tiers import TierClient

logger = logging.getLogger(__name__)

_POLICIES = ("affinity", "load", "random")


def replica_name(i: int) -> str:
    return f"r{i}"


def _split_devices(devices: List, n: int, tp: int) -> List[List]:
    """Per-replica device groups: when the tier's submesh has at least
    ``n × tp`` devices each replica gets its own contiguous ``tp``-chip
    slice (the data-parallel carve — replicas are the 'batch' axis of
    the SNIPPETS.md NamedSharding/P('batch') shape, realized as disjoint
    submeshes because each replica runs its own engine); otherwise every
    replica shares the whole group (process-local replicas — the CPU /
    single-chip box)."""
    per = max(1, tp)
    if len(devices) >= n * per:
        return [devices[i * per:(i + 1) * per] for i in range(n)]
    if per == 1 and devices:
        # Fewer devices than replicas: pin each replica to ONE device
        # round-robin (an unsharded replica must never grow a mesh just
        # because the box is short — extra replicas time-share).
        return [[devices[i % len(devices)]] for i in range(n)]
    return [list(devices) for _ in range(n)]


class _Replica:
    """One live member: the stable replica id (metric/breaker identity,
    minted monotonically, never reused), the request client, and the
    engine manager.  Records are immutable once published — membership
    changes replace/append records, never mutate them."""

    __slots__ = ("rid", "client", "mgr")

    def __init__(self, rid: int, client: TierClient, mgr: EngineManager):
        self.rid = rid
        self.client = client
        self.mgr = mgr

    @property
    def name(self) -> str:
        return replica_name(self.rid)


class ReplicaSetManager:
    """The EngineManager-shaped facade over a tier's replica managers.

    Everything that used to talk to ``tier.server_manager`` — the bench
    harness's start/stop between configs, Router.drain, GET /health —
    keeps working: lifecycle verbs fan out to every replica, liveness
    reads aggregate, and ``health()``/``kv_stats()``/``slot_stats()``
    return tier-level aggregates carrying a per-replica breakdown.
    Probe-surface methods stay lock-free exactly like EngineManager's
    (each sub-manager's health/is_server_running already are), and all
    of them iterate a SNAPSHOT of the member list so dynamic membership
    (scale_to) can change it mid-flight."""

    def __init__(self, tier: TierConfig,
                 managers: Optional[Sequence[EngineManager]] = None,
                 members: Optional[List[_Replica]] = None,
                 standby: Optional[List[_Replica]] = None):
        self.tier = tier
        if members is not None:
            # The SAME list object the ReplicatedTierClient mutates —
            # membership has one source of truth, not two views that
            # could drift.
            self._members = members
        else:
            self._members = [_Replica(i, None, m)
                             for i, m in enumerate(managers or [])]
        # Warm standby pool, shared by reference with the client's
        # scale_to (same one-source-of-truth rule): start_server warms
        # these alongside the sibling members, stop_server stops them.
        # NOT part of the serving surface — health/kv/slot aggregates
        # and drain cover MEMBERS only (a parked engine serves nothing).
        self._standby = standby if standby is not None else []

    # -- replica access -----------------------------------------------------

    @property
    def managers(self) -> List[EngineManager]:
        """Snapshot of the per-replica EngineManagers (historic
        attribute surface, now derived from the member records)."""
        return [r.mgr for r in list(self._members)]

    def replica_managers(self) -> List[EngineManager]:
        """The per-replica EngineManagers — the HealthMonitor's probe and
        restart targets (one wedged replica restarts alone)."""
        return self.managers

    def replica_items(self) -> List[Tuple[int, EngineManager]]:
        """(rid, manager) snapshot — the membership-stable iteration for
        probe keys and metric labels: rids never shift on removal, so
        ``nano/r1`` keeps meaning the same engine across scale events."""
        return [(r.rid, r.mgr) for r in list(self._members)]

    def live_engines(self) -> List[Tuple[str, Any]]:
        """(replica key, engine) for every RUNNING replica — the obs
        surfaces' iteration point (profiler trace, sampler, /stats).
        Never lazy-starts an engine."""
        out = []
        for r in list(self._members):
            engine = getattr(r.mgr, "_engine", None)
            if engine is not None:
                out.append((r.name, engine))
        return out

    # -- lifecycle (ServerManager surface) ----------------------------------

    def start_server(self, beat=None) -> None:
        """Start every replica (idempotent per replica).  Replica 0
        warms FIRST and alone — its warmup populates the in-process XLA
        compile cache — then the siblings AND the warm-standby pool
        warm CONCURRENTLY against that warm cache (the same
        deferred-go-live warm path scale-up rides): concurrent COLD
        compiles of the same programs would just contend, but cache-hit
        warmups only pay tracing.  Standbys warm here, at startup,
        precisely so scale-up never traces mid-peak."""
        members = list(self._members)
        if not members:
            return
        members[0].mgr.start_server(beat=beat)
        rest = members[1:] + list(self._standby)
        if not rest:
            return
        # Every key pre-populated BEFORE the workers start (value
        # overwrites only — safe under the GIL, never a size-changing
        # insert racing the error scan below).
        errors: Dict[int, Optional[BaseException]] = {
            r.rid: None for r in rest}
        threads = []
        for r in rest:
            def _start(r=r):
                try:
                    r.mgr.start_server()
                except BaseException as exc:
                    errors[r.rid] = exc
            t = threading.Thread(target=_start, daemon=True,
                                 name=f"warm-{self.tier.name}-{r.name}")
            threads.append(t)
            t.start()
        # ``beat`` fires from the JOINING loop, not the workers — the
        # bench watchdog's beat callback is not promised thread-safe.
        for t in threads:
            while t.is_alive():
                t.join(timeout=0.5)
                if beat is not None:
                    beat()
        for r in rest:
            if errors[r.rid] is not None:
                raise errors[r.rid]

    def stop_server(self) -> None:
        for mgr in self.managers:
            mgr.stop_server()
        for rec in list(self._standby):
            rec.mgr.stop_server()

    def drain(self, timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Drain every replica CONCURRENTLY and wait them all out — the
        tier is drained only when its last replica is (each replica
        stops admitting immediately, so the concurrent fan-out never
        extends the deadline past one replica's drain_timeout_s plus
        join slack).  Returns the aggregate summary with the per-replica
        breakdown."""
        timeout = (timeout_s if timeout_s is not None
                   else self.tier.drain_timeout_s)
        t0 = time.monotonic()
        members = list(self._members)
        # Every key pre-populated BEFORE the workers start: a worker
        # abandoned past the join bound may still finish later, and its
        # write must be a value OVERWRITE (safe under the GIL), never a
        # size-changing insert racing the summary's iteration below.
        results: Dict[str, Any] = {
            r.name: {"error": "Request failed: replica drain "
                     "did not return within the join bound"}
            for r in members}
        threads = []
        for r in members:
            def _drain(key=r.name, mgr=r.mgr):
                try:
                    results[key] = mgr.drain(timeout_s=timeout)
                except Exception as exc:   # a dead replica must not
                    results[key] = {"error": f"Request failed: {exc}"}
            t = threading.Thread(target=_drain, daemon=True,
                                 name=f"drain-{self.tier.name}-{r.name}")
            threads.append(t)
            t.start()
        deadline = time.monotonic() + max(0.0, float(timeout)) + 30.0
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        summary = {
            "draining_started": True,
            "in_flight_at_start": sum(
                int(r.get("in_flight_at_start", 0))
                for r in results.values() if isinstance(r, dict)),
            "drained": sum(int(r.get("drained", 0))
                           for r in results.values()
                           if isinstance(r, dict)),
            "aborted": sum(int(r.get("aborted", 0))
                           for r in results.values()
                           if isinstance(r, dict)),
            "waited_s": round(time.monotonic() - t0, 3),
            "replicas": dict(results),      # snapshot, not the live dict
        }
        return summary

    @property
    def draining(self) -> bool:
        """The TIER is draining only when every replica is: a partially
        drained tier still serves traffic on the survivors."""
        members = list(self._members)
        return bool(members) and all(r.mgr.draining for r in members)

    def is_server_running(self) -> bool:
        return any(m.is_server_running() for m in self.managers)

    def engine(self):
        """Single-engine compatibility accessor (bench legs and tests
        that introspect ``server_manager.engine()``): the first live
        member's engine, lazy-started like EngineManager.engine()."""
        return list(self._members)[0].mgr.engine()

    # -- aggregate observability --------------------------------------------

    def health(self) -> Dict[str, Any]:
        """Tier-level health = aggregate over per-replica health():
        ``ok`` while ANY replica serves (one wedged replica is degraded
        capacity, not a dead tier), ``wedged`` only when every replica
        is, capacity counters, and the full per-replica breakdown."""
        members = list(self._members)
        reps: Dict[str, Dict[str, Any]] = {}
        for r in members:
            try:
                reps[r.name] = r.mgr.health()
            except Exception as exc:
                reps[r.name] = {"ok": False,  # dllm-lint: disable=error-shape -- health-probe snapshot (GET /health surface), not the tier error path
                                "error": str(exc)[:200]}
        healthy = sum(1 for h in reps.values() if h.get("ok"))
        running = sum(1 for h in reps.values() if h.get("uptime_s"))
        entry: Dict[str, Any] = {
            "ok": healthy > 0,
            "draining": self.draining,
            "tier": self.tier.name,
            "model": self.tier.model_preset,
            "uptime_s": max((h.get("uptime_s") or 0.0)
                            for h in reps.values()) if reps else 0.0,
            "devices": None,
            "replica_count": len(members),
            "healthy_replicas": healthy,
            "degraded": 0 < healthy < len(members),
            "queue_depth": sum(int(h.get("queue_depth") or 0)
                               for h in reps.values()),
            "active_slots": sum(int(h.get("active_slots") or 0)
                                for h in reps.values()),
            "max_slots": sum(int(h.get("max_slots") or 0)
                             for h in reps.values()),
            "replicas": reps,
        }
        devices = [d for h in reps.values()
                   for d in (h.get("devices") or ())]
        if devices:
            entry["devices"] = devices
        if entry["max_slots"]:
            entry["slot_occupancy"] = round(
                entry["active_slots"] / entry["max_slots"], 3)
        if reps and all(h.get("wedged") for h in reps.values()):
            # Every replica stalled: the tier as a whole is wedged (the
            # per-replica watchdog verdicts still drive the individual
            # restarts — this flag is the operator's summary).
            entry["ok"] = False
            entry["wedged"] = True
        if running and not healthy:
            entry["error"] = "no healthy replica (all wedged or failed)"
        return entry

    def kv_stats(self) -> Optional[Dict[str, Any]]:
        """Summed block-pool picture over the live paged replicas, with
        the per-replica breakdown; None when no live replica has a paged
        pool (sequential engines).  ``dedup_ratio`` reports the MAX
        across replicas — the per-replica ratios are the meaningful
        series (block pools are disjoint; averaging them would hide a
        replica whose pool sharing collapsed)."""
        reps: Dict[str, Dict[str, Any]] = {}
        for key, engine in self.live_engines():
            fn = getattr(engine, "kv_stats", None)
            if callable(fn):
                try:
                    reps[key] = fn()
                except Exception:
                    pass
        if not reps:
            return None
        summed = ("free_blocks", "reclaimable_blocks", "total_blocks",
                  "preempted_total", "prefill_pending_blocks",
                  "prefill_backlog_tokens", "shared_blocks",
                  "pinned_entries")
        out: Dict[str, Any] = {k: sum(int(r.get(k, 0))
                                      for r in reps.values())
                               for k in summed}
        first = next(iter(reps.values()))
        out["block_size"] = first.get("block_size")
        out["dedup_ratio"] = max(float(r.get("dedup_ratio", 1.0))
                                 for r in reps.values())
        # Hierarchical-KV spill tier (ISSUE 14): host-tier occupancy and
        # demote/promote counters sum like the pool fields, but only
        # when some replica actually runs a spill tier — a spill-less
        # tier's aggregate keeps its historical shape.  (Affinity
        # already treats a replica's DEMOTED entries as eligible: the
        # per-engine prefix_affinity_tokens peek consults the spill
        # store, so a session follows its spilled prefix home.)
        spill_keys = ("host_entries", "host_blocks", "host_bytes",
                      "host_budget_bytes", "demotions_total",
                      "promotions_total", "promotion_races_total",
                      "demote_inflight", "promote_backlog_blocks")
        for k in spill_keys:
            if any(k in r for r in reps.values()):
                out[k] = sum(int(r.get(k, 0)) for r in reps.values())
        out["replicas"] = reps
        return out

    def slot_stats(self) -> Dict[str, Any]:
        """Summed occupancy over live replicas with per-replica rows."""
        reps: Dict[str, Dict[str, Any]] = {}
        for key, engine in self.live_engines():
            fn = getattr(engine, "slot_stats", None)
            if callable(fn):
                try:
                    reps[key] = fn()
                except Exception:
                    pass
        summed = ("queue_depth", "active_slots", "max_slots",
                  "preempted_total", "prefill_inflight",
                  "prefill_backlog_tokens")
        out: Dict[str, Any] = {k: sum(int(r.get(k, 0))
                                      for r in reps.values())
                               for k in summed}
        out["slot_occupancy"] = round(
            out["active_slots"] / max(1, out["max_slots"]), 3)
        out["replicas"] = reps
        return out

    def prefix_affinity(self, history) -> int:
        """Best parked-prefix match across the live replicas — the
        tier-level probe the Router's cross-TIER affinity steering
        consults (serving/router.py _apply_prefix_affinity): the tier
        holds a conversation's prefix if ANY replica does.  Tokenizes
        once, peeks each replica (non-destructive)."""
        best = 0
        ids = None
        for _key, engine in self.live_engines():
            peek = getattr(engine, "prefix_affinity_tokens", None)
            if not callable(peek):
                continue
            try:
                if ids is None:
                    ids = engine.affinity_token_ids(history)
                best = max(best, int(peek(ids)))
            except Exception:
                continue
        return best


class _ReplicaStream:
    """Stream wrapper feeding the replica breaker its COMPLETION verdict
    (the same rule as the Router's tier-level on_done: setup only proves
    one primed token, so a mid-decode death must reach the breaker as
    the failure it is; a consumer disconnect is not the replica's
    fault).  Transparent to RoutedStream: iteration and ``.result``
    forward to the tier handle."""

    def __init__(self, handle, on_done):
        self._handle = handle
        self._on_done = on_done
        self._fired = False

    def _fire(self, ok: bool) -> None:
        if not self._fired:
            self._fired = True
            try:
                self._on_done(ok)
            except Exception:
                pass

    def __iter__(self):
        try:
            for delta in self._handle:
                yield delta
        except GeneratorExit:
            self._fire(True)              # client disconnect: replica fine
            raise
        except BaseException:
            self._fire(False)
            raise
        self._fire(True)

    @property
    def result(self):
        return self._handle.result


def fail_captured(reqs: Sequence[Any], tier_name: str) -> int:
    """Last-resort release of a rescue capture (ISSUE 20): no sibling
    adopted the requests and the restarted engine cannot take them, so
    each fails with the engine-stopped error shape — the pre-rescue
    outcome.  Blocked callers unblock, streams see the end-of-stream
    sentinel.  Returns the number failed."""
    from ..engine.batching import EngineStoppedError
    n = 0
    for req in reqs:
        req.error = EngineStoppedError(error_dict(
            f"Request failed: tier {tier_name} engine stopped "
            f"mid-flight"))
        tq = getattr(req, "token_queue", None)
        if tq is not None:
            tq.put(None)
        req.done.set()
        n += 1
    return n


class ReplicatedTierClient:
    """The tier client over N replica TierClients — same surface as
    TierClient (``process`` / ``process_stream`` / ``load_snapshot`` /
    ``server_manager`` / ``tier`` / ``name``), with dispatch choosing a
    replica per request (module docstring: affinity → least-loaded, with
    the per-replica breaker veto) and membership actuatable at runtime
    (``scale_to`` — the autoscaler's verb)."""

    def __init__(
        self,
        tier: TierConfig,
        cluster: ClusterConfig,
        mesh=None,
        devices: Optional[List] = None,
        fault_injector: Optional[FaultInjector] = None,
        warmup_on_start: bool = True,
        seed: int = 0,
    ):
        if tier.replicas < 1:
            raise ValueError(f"tier {tier.name}: replicas must be >= 1, "
                             f"got {tier.replicas}")
        if tier.ep > 1 or tier.sp > 1:
            # Replica submeshes are tp-only: silently serving without
            # the configured expert/sequence sharding would look like
            # ep/sp is in effect while it is not (same warn-and-degrade
            # policy as _fit_sp's engine-mismatch rule).
            logger.warning(
                "tier %s: ep=%d sp=%d IGNORED — replicated tiers build "
                "tp-only submeshes per replica (replicas=%d wins); set "
                "replicas=1 to keep expert/sequence parallelism",
                tier.name, tier.ep, tier.sp, tier.replicas)
        self.tier = tier
        self.name = tier.name
        self.faults = fault_injector
        n = tier.replicas
        if getattr(tier, "autoscale", False):
            # Elastic tiers start at the autoscaler's capacity floor
            # (min may exceed the static replicas field, which is then
            # just the pre-elastic default).
            n = max(n, int(getattr(tier, "autoscale_min_replicas", 1)))
        self._devices = (list(mesh.devices.flat) if mesh is not None
                         else list(devices or []))
        from ..parallel.mesh import requested_tp
        self._tp_req = requested_tp(tier)
        self._seed = seed
        self._warmup_on_start = warmup_on_start
        groups = _split_devices(self._devices, n, self._tp_req)
        # Membership: ONE list of member records, shared by reference
        # with the ReplicaSetManager below.  Mutations are atomic list
        # ops under _scale_lock; every reader takes list() snapshots.
        self._members: List[_Replica] = []
        self._next_rid = 0
        # Scale serialization: the lock guards only the BUSY FLAG, never
        # the minutes-long warm/quiesce work itself — a scale operation
        # blocks on compiles and drains, and holding a lock across that
        # would stall any operator/autoscaler caller (and trips the
        # lock-blocking-call lint).  Membership mutations stay atomic
        # list ops; readers take list() snapshots.
        self._scale_lock = threading.Lock()
        self._scaling = False
        for i in range(n):
            group = groups[i] if i < len(groups) else self._devices
            self._members.append(self._build_replica(self._mint_rid(),
                                                     group))
        # Warm standby pool (autoscale tiers): the replicas between min
        # and max are BUILT here and WARMED by start_server, parked
        # off-membership.  scale_to(up) then publishes a warm standby in
        # milliseconds instead of tracing an engine mid-peak, and
        # scale_to(down) parks the drained replica for the next peak.
        # The pool shares by reference with the ReplicaSetManager below
        # (one source of truth, like the member list).
        self._standby: List[_Replica] = []
        if getattr(tier, "autoscale", False) and \
                getattr(tier, "autoscale_warm_pool", False):
            n_max = max(n, int(getattr(tier, "autoscale_max_replicas", n)))
            for k in range(n, n_max):
                self._standby.append(self._build_replica(
                    self._mint_rid(), self._device_group(k, n_max)))
        self.server_manager = ReplicaSetManager(tier,
                                                members=self._members,
                                                standby=self._standby)
        # Per-replica breaker sub-gate: same thresholds as the cluster's
        # tier-level breaker; breaker_failures=0 disables both.  The
        # tier-level breaker (Router) still owns whole-tier shedding —
        # this one only steers dispatch AWAY from a failing replica
        # while the survivors keep the tier closed.
        self.breaker = CircuitBreaker(
            [r.name for r in self._members],
            failure_threshold=getattr(cluster, "breaker_failures", 0),
            cooldown_s=getattr(cluster, "breaker_cooldown_s", 30.0))
        self._rr_lock = threading.Lock()
        self._rr = 0
        self._rng = random.Random(seed ^ 0x5EED)
        self._last_client: Optional[TierClient] = None
        # Observability sink, lazily resolved so tests/bench can inject
        # a fresh registry after construction (same pattern as the
        # manager's global fallbacks).
        self.obs = None

    # -- membership ----------------------------------------------------------

    @property
    def clients(self) -> List[TierClient]:
        """Snapshot of the live replica clients (historic attribute
        surface, now derived from the member records)."""
        return [r.client for r in list(self._members)]

    def replica_count(self) -> int:
        return len(self._members)

    def _mint_rid(self) -> int:
        rid = self._next_rid
        self._next_rid += 1
        return rid

    def _build_replica(self, rid: int, group: List) -> _Replica:
        """Construct one replica's EngineManager + TierClient (NOT yet
        published to membership, NOT yet started)."""
        # Replica-suffixed tier identity for the ENGINE side: logs,
        # per-replica metric labels (dllm_decode_tick_ms{tier=
        # "nano/r0"}, the per-replica compiled-programs gauge the
        # bench leg pins), profiler timelines.  The CLIENT keeps the
        # base name: error shapes, fault targeting, and trace spans
        # must stay byte-identical to the single-replica tier.
        rtier = dataclasses.replace(
            self.tier, name=f"{self.tier.name}/{replica_name(rid)}")
        if len(group) > 1:
            from ..parallel.mesh import tp_mesh
            # Multi-device group = this replica's own TP submesh,
            # at the TIER's tp degree (a short box sharing devices
            # must not inflate tp past the config).
            mgr = EngineManager(
                rtier,
                mesh=tp_mesh(group,
                             min(max(1, self._tp_req), len(group))),
                seed=self._seed, warmup_on_start=self._warmup_on_start)
        else:
            mgr = EngineManager(rtier, devices=(group or None),
                                seed=self._seed,
                                warmup_on_start=self._warmup_on_start)
        client = TierClient(rtier, mgr, self.faults)
        client.name = self.tier.name  # base-name error/fault identity
        return _Replica(rid, client, mgr)

    def scale_to(self, n: int, reason: str = "manual",
                 timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Actuate membership to ``n`` replicas (bounded below at 1).
        One scale operation at a time — a busy flag claimed under
        ``_scale_lock``; an overlapping call returns immediately with a
        ``busy`` error rather than queueing behind minutes of warmup
        (the autoscaler treats a refused actuation as retryable).
        Dispatch is NEVER blocked, because membership reads are
        lock-free snapshots and the blocking warm/quiesce work runs
        with no lock held.

        Scale-UP builds the new replicas off-membership and warms them
        CONCURRENTLY and fully (start_server → engine warmup, riding
        the process XLA compile cache an existing replica populated)
        before publishing: deferred go-live — dispatch never sees a
        replica that would block on a cold compile or pay first-touch
        traces mid-peak (a half-warm replica trades cheap actuation
        for a trace storm exactly when the tier is saturated).

        Scale-DOWN retires the least-affine replica: membership removal
        first (no new dispatch), bounded quiesce of in-flight work,
        demote of its refcount-1 parked prefixes through the host spill
        tier with the resident entries HANDED OFF to a survivor's store
        (the shrink costs warm TTFT only where no spill tier exists,
        never correctness), then PR 5 drain-and-stop."""
        n = max(1, int(n))
        summary: Dict[str, Any] = {"target": n, "reason": reason,
                                   "added": [], "removed": [],
                                   "errors": []}
        with self._scale_lock:
            if self._scaling:
                summary["errors"].append("busy: scale in progress")
                summary["replicas"] = len(self._members)
                return summary
            self._scaling = True
        try:
            cur = len(self._members)
            if cur < n:
                self._scale_up(n, summary)
            elif cur > n:
                while len(self._members) > n:
                    info = self._scale_down_one(timeout_s)
                    if info is None:
                        break
                    summary["removed"].append(info)
        finally:
            with self._scale_lock:
                self._scaling = False
        summary["replicas"] = len(self._members)
        return summary

    def _scale_up(self, n: int, summary: Dict[str, Any]) -> None:
        """Add members up to ``n`` (busy flag claimed, no lock held):
        publish warm standbys first (already built and warmed — go-live
        is a breaker key + an atomic append, milliseconds), then build
        and warm any remainder concurrently and publish the
        survivors."""
        while len(self._members) < n and self._standby:
            r = self._standby.pop(0)
            try:
                if self.faults is not None:
                    # Injected warm-standby publish failure (ISSUE 20
                    # fault matrix): the parked engine's device went
                    # away — the publish raises, the handler below
                    # retires the handle, and the loop falls through
                    # to building fresh capacity.
                    fail = self.faults.standby_publish_fail(self.name)
                    if fail is not None:
                        raise RuntimeError(fail)
                r.mgr.start_server()     # idempotent; no-op when warm
                # ensure() is inside the handler's reach: the handle is
                # neither standby nor member here, so any raise before
                # the append below must stop the server or it leaks.
                self.breaker.ensure(r.name)
            except BaseException as exc:
                try:
                    r.mgr.stop_server()
                except Exception:
                    pass
                summary["errors"].append(f"{r.name}: {exc}")
                continue
            self._members.append(r)
            summary["added"].append(r.name)
            logger.info(
                "tier %s: replica %s live (scale-up from warm "
                "standby, %s)", self.name, r.name,
                summary.get("reason"))
        count = len(self._members)
        fresh = []
        for k in range(n - count):
            group = self._device_group(count + k, n)
            fresh.append(self._build_replica(self._mint_rid(), group))
        errors: Dict[int, Optional[BaseException]] = {
            r.rid: None for r in fresh}
        threads = []
        for r in fresh:
            def _warm(r=r):
                try:
                    r.mgr.start_server()
                except BaseException as exc:
                    errors[r.rid] = exc
            t = threading.Thread(target=_warm, daemon=True,
                                 name=f"warm-{self.name}-{r.name}")
            threads.append(t)
            t.start()
        for t in threads:
            t.join()
        for r in fresh:
            if errors[r.rid] is not None:
                summary["errors"].append(
                    f"{r.name}: {errors[r.rid]}")
                try:
                    r.mgr.stop_server()
                except Exception:
                    pass
                continue
            # Go-live: breaker key first (a keyless replica would be
            # waved through ungated), then the atomic membership append.
            self.breaker.ensure(r.name)
            self._members.append(r)
            summary["added"].append(r.name)
            logger.info("tier %s: replica %s live (scale-up, %s)",
                        self.name, r.name, summary.get("reason"))

    def _device_group(self, slot: int, count: int) -> List:
        """The device slice for a NEW replica taking position ``slot``
        of ``count``: the same carve rule as construction, recomputed at
        the new width.  Existing replicas keep the groups they were
        built with — only the new slot's slice is consulted, and on the
        shared-device (CPU / single-chip) box every slice is the whole
        group anyway."""
        groups = _split_devices(self._devices, count, self._tp_req)
        return groups[slot] if slot < len(groups) else self._devices

    def _pick_victim(self) -> Optional[_Replica]:
        """The least-affine live replica: fewest parked prefix tokens
        (its warm state is the cheapest to walk away from), ties broken
        by least in-flight work, then youngest rid (the most recently
        added capacity goes first)."""
        members = list(self._members)
        if len(members) <= 1:
            return None

        def score(rec: _Replica):
            parked = 0
            engine = getattr(rec.mgr, "_engine", None)
            cache = getattr(engine, "prefix_cache", None)
            if cache is not None:
                try:
                    parked = sum(len(e.ids)
                                 for e in cache.entries_snapshot())
                except Exception:
                    parked = 0
            try:
                snap = rec.client.load_snapshot()
                busy = (int(snap.get("queue_depth", 0))
                        + int(snap.get("active_slots", 0)))
            except Exception:
                busy = 0
            return (parked, busy, -rec.rid)

        return min(members, key=score)

    def _scale_down_one(
            self, timeout_s: Optional[float]) -> Optional[Dict[str, Any]]:
        """Retire one replica (busy flag claimed).  Ordering is the
        correctness argument: (1) membership removal — no new dispatch;
        (2) bounded quiesce — finishing requests PARK their prefixes;
        (3) demote sweep + spill handoff — BEFORE drain flips the
        engine's ``_stop``, after which ``_try_demote`` stands down;
        (4) drain-and-stop; (5) breaker key retired."""
        victim = self._pick_victim()
        if victim is None:
            return None
        self._members.remove(victim)          # atomic: dispatch stops here
        try:
            return self._retire(victim, timeout_s)
        except BaseException:
            # The handle left membership above and was never re-homed
            # (standby parks and drain-stop both return normally), so
            # this unwind is the last reference to a live server.
            if victim not in self._standby:
                try:
                    victim.mgr.stop_server()
                except Exception:
                    pass
                self.breaker.forget(victim.name)
            raise

    def _retire(
            self, victim: _Replica,
            timeout_s: Optional[float]) -> Optional[Dict[str, Any]]:
        """Quiesce → demote/handoff → park-or-drain one removed member
        (the body of ``_scale_down_one``; the caller owns the unwind)."""
        timeout = (timeout_s if timeout_s is not None
                   else self.tier.drain_timeout_s)
        deadline = time.monotonic() + max(0.5, float(timeout))
        while time.monotonic() < deadline:
            try:
                snap = victim.client.load_snapshot()
                if not snap.get("queue_depth") \
                        and not snap.get("active_slots"):
                    break
            except Exception:
                break
            time.sleep(0.05)
        demoted = handed = 0
        engine = getattr(victim.mgr, "_engine", None)
        if engine is not None:
            sweep = getattr(engine, "demote_parked", None)
            if callable(sweep):
                try:
                    demoted = int(sweep() or 0)
                except Exception:
                    demoted = 0
            spill = getattr(engine, "kv_spill", None)
            if spill is not None:
                try:
                    spill.flush(timeout_s=5.0)
                except Exception:
                    pass
                target = self._spill_target(exclude=victim)
                if target is not None:
                    try:
                        for ids, tiles, nbytes, nb in \
                                spill.export_resident():
                            if target.admit_resident(ids, tiles,
                                                     nbytes, nb):
                                handed += 1
                    except Exception:
                        logger.exception(
                            "tier %s: spill handoff from %s failed",
                            self.name, victim.name)
        # Warm pool: a QUIESCED victim parks (engine kept warm,
        # off-membership) instead of draining to destruction — the next
        # scale-up republishes it in milliseconds.  A victim still busy
        # at the deadline is NOT parked: parking an engine with live
        # work would hide in-flight requests from every serving
        # aggregate, so it falls through to the full drain-and-stop.
        parked = False
        if getattr(self.tier, "autoscale", False) and \
                getattr(self.tier, "autoscale_warm_pool", False):
            try:
                snap = victim.client.load_snapshot()
                parked = (not snap.get("queue_depth")
                          and not snap.get("active_slots"))
            except Exception:
                parked = False
        if parked:
            drain = None
            self._standby.append(victim)
        else:
            try:
                drain = victim.mgr.drain(
                    timeout_s=max(0.5, deadline - time.monotonic()))
            except Exception as exc:
                # A failed drain still retires the replica: without the
                # stop the server would outlive its membership with no
                # reference left to ever shut it down.
                try:
                    victim.mgr.stop_server()
                except Exception:
                    pass
                drain = {"error": f"Request failed: {exc}"}
        self.breaker.forget(victim.name)
        logger.info("tier %s: replica %s %s (scale-down; "
                    "%d entries demoted, %d handed off)",
                    self.name, victim.name,
                    "parked to warm standby" if parked else "retired",
                    demoted, handed)
        return {"replica": victim.name, "demoted_entries": demoted,
                "handed_off": handed, "parked": parked,
                "drained": (drain or {}).get("drained", 0)
                if isinstance(drain, dict) else 0}

    def _spill_target(self, exclude: _Replica):
        """A survivor's spill store for the retiring replica's resident
        entries — the first live member with one (host tiles are in
        pool layout, identical across same-config replicas)."""
        for rec in list(self._members):
            if rec is exclude:
                continue
            engine = getattr(rec.mgr, "_engine", None)
            spill = getattr(engine, "kv_spill", None)
            if spill is not None:
                return spill
        return None

    # -- crash rescue (ISSUE 20) --------------------------------------------

    def restart_replica(self, rid: int,
                        reason: str = "wedged") -> Dict[str, Any]:
        """Restart ONE replica's engine with crash rescue: the victim's
        queued + in-flight requests are captured (prompt + generated
        prefix, the PR 5 replay machinery) and re-dispatched to a live
        sibling — or re-queued on the restarted engine when the tier
        has one replica — resuming byte-identically under greedy, and
        the host spill store survives the restart (detached before
        ``stop_server``, re-attached after, or handed to a survivor).

        Serialized through the SAME busy flag as ``scale_to``: a restart
        racing a scale-down would strand a freshly rebuilt engine
        outside the membership, so an overlapping call returns a
        ``busy`` error instead — the HealthMonitor keeps the replica's
        failure streak and retries next probe, the same contract as a
        refused autoscaler actuation."""
        summary: Dict[str, Any] = {
            "replica": replica_name(rid), "reason": reason,
            "restarted": False, "rescued": 0, "outcome": None,
            "spill_reattached": False, "errors": []}
        with self._scale_lock:
            if self._scaling:
                summary["errors"].append("busy: scale in progress")
                return summary
            self._scaling = True
        try:
            victim = next(
                (r for r in list(self._members) if r.rid == rid), None)
            if victim is None:
                summary["errors"].append(
                    f"{replica_name(rid)}: not a member")
                return summary
            engine = getattr(victim.mgr, "_engine", None)
            spill = None
            if getattr(self.tier, "spill_survive_restart", True) \
                    and hasattr(engine, "detach_spill"):
                spill = engine.detach_spill()
            captured: List[Any] = []
            if getattr(self.tier, "replica_rescue", True) \
                    and hasattr(engine, "capture_requests"):
                captured = engine.capture_requests()
            self._rescue_and_restart(victim, captured, spill, summary)
            return summary
        finally:
            with self._scale_lock:
                self._scaling = False

    def _rescue_and_restart(self, victim: _Replica, captured: List[Any],
                            spill: Any,
                            summary: Dict[str, Any]) -> None:
        """Restart ``victim``'s engine and re-home its captured work
        (busy flag claimed).  Rescue runs FIRST when a sibling lives —
        MTTR is then one capture + adopt, not an engine rebuild — so
        the restart's minutes never sit between a stalled stream and
        its resumption."""
        sibling = None
        if captured:
            for rec in list(self._members):
                if rec is victim or not rec.mgr.is_server_running():
                    continue
                eng = getattr(rec.mgr, "_engine", None)
                if callable(getattr(eng, "adopt_requests", None)):
                    sibling = rec
                    break
            if sibling is not None:
                adopted = sibling.mgr._engine.adopt_requests(captured)
                self._note_rescue(captured, "sibling", sibling.name)
                summary["rescued"] = adopted
                summary["outcome"] = "sibling"
        try:
            victim.mgr.stop_server()
            victim.mgr.start_server()
            summary["restarted"] = True
        except Exception as exc:
            summary["errors"].append(f"{victim.name}: restart: {exc}")
        new_engine = (getattr(victim.mgr, "_engine", None)
                      if summary["restarted"] else None)
        if spill is not None:
            adopt = getattr(new_engine, "adopt_spill", None)
            if callable(adopt) and adopt(spill):
                summary["spill_reattached"] = True
                try:
                    m = (self.obs or get_observability()).m
                    m.spill_reattach.labels(self.name).inc()
                except Exception:
                    pass
            else:
                # The rebuilt engine refused (restart failed, or the
                # geometry changed): hand the warm entries to a
                # survivor through the scale-down handoff path, then
                # stop the orphan store.
                target = self._spill_target(exclude=victim)
                handed = 0
                if target is not None:
                    try:
                        for ids, tiles, nbytes, nb in \
                                spill.export_resident():
                            if target.admit_resident(ids, tiles,
                                                     nbytes, nb):
                                handed += 1
                    except Exception:
                        logger.exception(
                            "tier %s: spill handoff from %s failed",
                            self.name, victim.name)
                summary["spill_handed_off"] = handed
                try:
                    spill.stop()
                except Exception:
                    pass
        if captured and sibling is None:
            adopt_reqs = getattr(new_engine, "adopt_requests", None)
            if callable(adopt_reqs):
                summary["rescued"] = adopt_reqs(captured)
                summary["outcome"] = "requeue"
                self._note_rescue(captured, "requeue", victim.name)
            else:
                fail_captured(captured, self.name)
                summary["outcome"] = "failed"
                self._note_rescue(captured, "failed", victim.name)
        if summary["restarted"]:
            self.breaker.reset(replica_name(victim.rid))
        logger.info(
            "tier %s: replica %s restarted=%s rescued=%d (%s) "
            "spill_reattached=%s (%s)", self.name, victim.name,
            summary["restarted"], summary["rescued"], summary["outcome"],
            summary["spill_reattached"], summary["reason"])

    def _note_rescue(self, captured: List[Any], outcome: str,
                     by: str) -> None:
        """Rescue observability: one counter bump per request plus a
        ``rescue`` span event so flight-recorder entries show who saved
        the request."""
        try:
            m = (self.obs or get_observability()).m
            m.replica_rescues.labels(self.name, outcome).inc(
                len(captured))
        except Exception:
            pass
        for req in captured:
            obs_spans.event(getattr(req, "trace", None), "rescue",
                            tier=self.name, outcome=outcome, by=by)

    # -- dispatch policy ----------------------------------------------------

    def _policy(self) -> str:
        raw = (env_str("DLLM_REPLICA_POLICY") or "").strip().lower()
        if raw in _POLICIES:
            return raw
        return "affinity" if self.tier.replica_affinity else "load"

    def _predicted_waits(self) -> List[Tuple[float, int]]:
        """(predicted queue wait s, inflight) per replica — PR 1's
        admission predictor (queue_depth / slots × EWMA service time)
        read from each replica's own controller."""
        out = []
        for c in self.clients:
            snap = c.admission.snapshot()
            ewma_s = (snap.get("ewma_service_ms") or 0.0) / 1000.0
            wait = (snap["queue_depth"] / max(1, snap["slots"])) * ewma_s
            out.append((wait, int(snap["inflight"])))
        return out

    def _affinity_scores(self, history) -> List[int]:
        """Parked-prefix match tokens per replica: tokenize ONCE with
        the first live engine, peek every live replica's cache with the
        same ids (stopped replicas score 0 — the probe never starts an
        engine)."""
        members = list(self._members)
        scores = [0] * len(members)
        ids = None
        for i, r in enumerate(members):
            engine = getattr(r.mgr, "_engine", None)
            peek = getattr(engine, "prefix_affinity_tokens", None)
            if not callable(peek) \
                    or getattr(engine, "prefix_cache", None) is None:
                continue            # no cache → never pay tokenization
            try:
                if ids is None:
                    ids = engine.affinity_token_ids(history)
                scores[i] = int(peek(ids))
            except Exception:
                scores[i] = 0
        return scores

    def _pick_replica(self, history,
                      members: Optional[List[_Replica]] = None
                      ) -> Tuple[int, str]:
        """(index into the membership snapshot, how) — how ∈ {single,
        affinity, affinity_overridden, least_loaded, random,
        breaker_fallback}.  Callers that must dereference the index
        pass their own snapshot as ``members`` (dispatch does), so a
        concurrent scale event can't shift what the index means."""
        if members is None:
            members = list(self._members)
        n = len(members)
        if n == 1:
            return 0, "single"
        waits = self._predicted_waits()
        if len(waits) < n:
            # A membership change landed between the snapshot and the
            # helper's read: pad — the extra members are brand-new and
            # empty, so zero predicted wait is the truth anyway.
            waits = waits + [(0.0, 0)] * (n - len(waits))
        with self._rr_lock:
            rr = self._rr
            self._rr += 1
            # Drawn under the lock even when unused: Random isn't
            # thread-safe, and drawing unconditionally keeps the
            # sequence deterministic per request index.
            shuffled = self._rng.sample(range(n), n)
        order = sorted(range(n),
                       key=lambda i: (waits[i][0], waits[i][1],
                                      (i - rr) % n))
        how = "least_loaded"
        policy = self._policy()
        if policy == "random":
            order = shuffled
            how = "random"
        elif policy == "affinity":
            scores = self._affinity_scores(history)
            if len(scores) < n:
                scores = scores + [0] * (n - len(scores))
            best = max(range(n), key=lambda i: (scores[i], -waits[i][0]))
            if scores[best] >= self.tier.replica_affinity_min_tokens:
                least = order[0]
                if (waits[best][0] - waits[least][0]
                        <= self.tier.replica_affinity_override_s):
                    order.remove(best)
                    order.insert(0, best)
                    how = "affinity"
                else:
                    # The affine replica is too hot: locality yields to
                    # load — re-prefilling elsewhere beats queuing here.
                    how = "affinity_overridden"
        for idx in order:
            if self.breaker.allow(members[idx].name):
                return idx, (how if idx == order[0]
                             else "breaker_fallback")
        # Every replica's circuit is open within cooldown: dispatch the
        # best candidate anyway — whole-tier shedding is the Router's
        # tier-level breaker's job, and a tier with replicas=1 has no
        # replica gate at all (parity).
        return order[0], "breaker_fallback"

    def _note_route(self, member: _Replica, how: str) -> None:
        obs_spans.annotate(current_trace(), replica=member.name,
                           replica_policy=how)
        try:
            m = (self.obs or get_observability()).m
            m.replica_routed.labels(self.name, how).inc()
        except Exception:
            pass

    def _feed_breaker(self, member, raw: Any) -> None:
        """Sync/setup outcome → the replica breaker.  Admission
        rejections are breaker-neutral (healthy backpressure; the PR 2
        rule) but repay a half-open canary permit.  ``member`` is the
        dispatched record — or a positional index into the current
        membership (the historic call shape tests drive directly)."""
        if isinstance(member, _Replica):
            key = member.name
        else:
            members = list(self._members)
            i = int(member)
            key = (members[i].name if 0 <= i < len(members)
                   else replica_name(i))
        if is_error_shape(raw):
            if "admission rejected" in str(raw.get("error", "")):
                self.breaker.release_probe(key)
            else:
                self.breaker.record(key, False)
        else:
            self.breaker.record(key, True)

    def reset_replica(self, rid: int) -> None:
        """Force-close one replica's circuit (the HealthMonitor calls
        this after successfully restarting that replica's engine)."""
        self.breaker.reset(replica_name(rid))

    def member_manager(self, rid: int) -> Optional[EngineManager]:
        """The EngineManager behind member ``rid``, or None when the rid
        left membership.  The HealthMonitor compares this against its
        probe snapshot by IDENTITY before routing a restart through
        ``restart_replica`` — a probe of one manager must never trigger
        a rescue-restart of a different one (tests swap duck-typed
        manager sets under the same tier client)."""
        for r in list(self._members):
            if r.rid == rid:
                return r.mgr
        return None

    def healthy_replicas(self) -> int:
        """Replicas currently able to serve: running, not draining, not
        watchdog-stalled, circuit not open.  Lock-free advisory reads
        only (the sampler calls this at cadence)."""
        n = 0
        for r in list(self._members):
            if not r.mgr.is_server_running() or r.mgr.draining:
                continue
            if self.breaker.state(r.name) == OPEN:
                continue
            engine = getattr(r.mgr, "_engine", None)
            stall = getattr(engine, "progress_stall_s", None)
            deadline = self.tier.watchdog_stall_s
            if callable(stall) and deadline is not None:
                try:
                    if float(stall()) > deadline:
                        continue
                except Exception:
                    pass
            n += 1
        return n

    # -- request surface (TierClient parity) --------------------------------

    def process(self, history) -> Dict[str, Any]:
        members = list(self._members)
        idx, how = self._pick_replica(history, members=members)
        member = members[min(idx, len(members) - 1)]
        self._note_route(member, how)
        client = member.client
        self._last_client = client
        raw = client.process(history)
        self._feed_breaker(member, raw)
        return raw

    def process_stream(self, history):
        members = list(self._members)
        idx, how = self._pick_replica(history, members=members)
        member = members[min(idx, len(members) - 1)]
        self._note_route(member, how)
        client = member.client
        self._last_client = client
        handle = client.process_stream(history)
        if is_error_shape(handle):
            self._feed_breaker(member, handle)
            return handle
        key = member.name
        return _ReplicaStream(
            handle, lambda ok: self.breaker.record(key, ok))

    def load_snapshot(self) -> Dict[str, Any]:
        """Tier-level load = sum over replicas (the queue-aware perf
        strategy and the cross-host load allgather read ONE row per
        tier; the per-replica split is dispatch's private signal)."""
        out = {"queue_depth": 0, "active_slots": 0, "max_slots": 0}
        for c in self.clients:
            snap = c.load_snapshot()
            for k in out:
                out[k] += int(snap.get(k, 0))
        return out

    @property
    def last_result(self):
        c = self._last_client
        return c.last_result if c is not None else None

    @property
    def admission(self):
        """The last-dispatched replica's controller (back-compat shim
        for tests poking ``tier.admission``); per-replica controllers
        live on each client in ``self.clients``."""
        c = self._last_client or self.clients[0]
        return c.admission
