"""Engine lifecycle manager — the in-process ServerManager.

The reference's ServerManager (src/models/server_manager.py) SSH-bootstraps a
remote Flask process, opens a tunnel, and polls TCP + /health before
declaring readiness.  With tiers as in-process engines on chip submeshes
there is no remote process, but the *capability* survives with the same
surface: ``start_server`` (build + compile + warm the engine; idempotent),
``stop_server`` (drop the engine, releasing its HBM), ``is_server_running``,
and a ``health()`` snapshot equivalent to the device servers' GET /health.
The benchmark harness drives exactly this surface between experiment configs
(reference: routing_chatbot_tester.py:388-394, 491-498).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, Optional, Sequence

import jax

from ..config import TierConfig
from ..obs import spans as obs_spans
from .inference import InferenceEngine

logger = logging.getLogger(__name__)


class TierOverCapacityError(RuntimeError):
    """A tier with ``hbm_gb_per_chip`` set does not fit its deployed
    submesh: params + KV per chip exceed the budget
    (utils/hbm_budget.tier_hbm_budget).  Raised by ``start_server``
    BEFORE any weights materialize, so the refusal is clean — no
    half-allocated engine, no device OOM mid-warmup.  The fix is a
    config change: raise ``tp`` (shard the footprint over more chips),
    shrink the model/KV, or clear the budget."""


class EngineManager:
    def __init__(
        self,
        tier: TierConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        seed: int = 0,
        warmup_on_start: bool = True,
    ):
        self.tier = tier
        self.mesh = mesh
        self.devices = devices
        self.seed = seed
        self.warmup_on_start = warmup_on_start
        self._engine: Optional[InferenceEngine] = None
        self._lock = threading.RLock()
        self._started_at: Optional[float] = None
        # Graceful drain (drain()): True from drain start until the next
        # start_server.  Single-word flag read lock-free by health() and
        # the admission gate; a draining tier is INTENTIONALLY shedding —
        # HealthMonitor and the breaker must not treat it as failure.
        self._draining = False
        # Watchdog-wedge edge detector: health() counts CLOSED→WEDGED
        # transitions (not every probe of a wedged engine) into the
        # global registry's dllm_watchdog_wedged_total.  Own lock: the
        # stall check deliberately runs OUTSIDE the lifecycle lock, and
        # concurrent health() callers (HealthMonitor probe + /stats)
        # must not double-count one wedge.
        self._wedged_seen = False
        self._wedged_lock = threading.Lock()

    # -- lifecycle (ServerManager surface) ---------------------------------

    def start_server(self, beat=None) -> None:
        """Idempotent: build the engine and compile/warm the hot paths.
        ``beat`` (optional liveness callback) is forwarded to the
        engine's warmup — on chip a full warmup is many multi-10s
        compiles, longer than a caller's wedge watchdog may wait.

        The lifecycle lock is held through the whole build/compile ON
        PURPOSE: it exists to serialize start/stop, and concurrent
        lazy-starts must collapse into one build.  The liveness surface
        (``health``/``is_server_running``) and the ``engine()`` fast
        path deliberately do NOT take it — a probe blocking here through
        a multi-minute compile would read as a dead tier (the PR 2 bug
        the lock-discipline lint now guards)."""
        with self._lock:
            if self._engine is not None:
                return
            # A restart re-opens a drained tier for traffic.
            self._draining = False
            admission = getattr(self, "admission", None)
            if admission is not None:
                try:
                    admission.end_drain()
                except Exception:
                    pass                     # stub controllers in tests
            t0 = time.perf_counter()
            if self.tier.hbm_gb_per_chip is not None:
                # Admission-time residency budget (PR 16): eval_shape
                # only — nothing materializes before the verdict.
                from ..utils.hbm_budget import tier_hbm_budget
                budget = tier_hbm_budget(
                    self.tier, devices=self.devices,
                    hbm_per_chip_gb=self.tier.hbm_gb_per_chip,
                    mesh=self.mesh)
                if not budget["fits"]:
                    raise TierOverCapacityError(
                        f"tier {self.tier.name}: "
                        f"{budget['total_gb_per_chip']} GB/chip "
                        f"(params {budget['params_gb_per_chip']} + KV "
                        f"{budget['kv_gb_per_chip']}) plus the 0.75 GB "
                        f"activation headroom exceeds the "
                        f"hbm_gb_per_chip={self.tier.hbm_gb_per_chip} "
                        f"budget on {budget['chips']} chip(s) — raise "
                        f"tp to shard the footprint over more chips")
                logger.info(
                    "tier %s: fits %s GB/chip budget (%s GB/chip over "
                    "%d chip(s), headroom %s GB)", self.tier.name,
                    self.tier.hbm_gb_per_chip,
                    budget["total_gb_per_chip"], budget["chips"],
                    budget["headroom_gb"])
            params = None
            if self.tier.checkpoint_path:
                from ..utils.checkpoint import load_params_for_tier
                params = load_params_for_tier(  # dllm-lint: disable=lock-blocking-call -- lifecycle lock intentionally held through the build; all liveness readers are lock-free (see docstring)
                    self.tier.checkpoint_path, self.tier.model(),
                    mesh=self.mesh, devices=self.devices)
                if beat is not None:
                    beat()
            use_speculative = bool(self.tier.draft_preset)
            if use_speculative and (self.tier.temperature > 0
                                    or (self.mesh is not None
                                        and self.tier.decode_batch <= 1)):
                # The SEQUENTIAL speculative engine stays unsharded; the
                # batched path (decode_batch>1) rides the ragged tick,
                # which PR 16 runs under shard_map on a TP mesh — a mesh
                # no longer disqualifies it.  Sampling still does: both
                # paths are greedy-exact.
                logger.warning(
                    "tier %s: draft_preset=%s ignored (sequential "
                    "speculative decoding is greedy-only and unsharded; "
                    "mesh=%s temperature=%s decode_batch=%d)",
                    self.tier.name, self.tier.draft_preset,
                    self.mesh is not None, self.tier.temperature,
                    self.tier.decode_batch)
                use_speculative = False
            if use_speculative and self.tier.decode_batch > 1:
                # Batched speculative path (ISSUE 15, retiring the PR 1
                # bypass): a configured draft with decode_batch>1 serves
                # through the continuous-batching engine — per-slot
                # drafts verified in one fused ragged call — instead of
                # falling back to the sequential engine and abandoning
                # concurrency.
                logger.info(
                    "tier %s: draft_preset=%s serves the BATCHED "
                    "speculative path (spec_decode armed; decode_batch=%d "
                    "slots, spec_gamma_max=%d)",
                    self.tier.name, self.tier.draft_preset,
                    self.tier.decode_batch, self.tier.spec_gamma_max)
                use_speculative = False
            if use_speculative:
                import dataclasses as _dc

                from .speculative import SpeculativeEngine
                # decode_batch=1 keeps the sequential speculative engine
                # (the batched path needs batch slots; set decode_batch>1
                # — and tune spec_decode / spec_gamma_max — to serve the
                # batched speculative path instead).
                logger.info(
                    "tier %s: decode_batch=1 — sequential SpeculativeEngine "
                    "(set decode_batch>1 for the batched speculative path; "
                    "spec_decode/spec_gamma_max govern it)", self.tier.name)
                # The draft is a fresh model: no draft-side checkpoint
                # exists (the target's weights are a different
                # architecture), so clear inherited paths.
                draft = _dc.replace(self.tier, name=f"{self.tier.name}-draft",
                                    model_preset=self.tier.draft_preset,
                                    draft_preset=None, checkpoint_path=None)
                engine = SpeculativeEngine(
                    self.tier, draft, gamma=self.tier.speculative_gamma,
                    seed=self.seed, target_params=params)
            elif self.tier.decode_batch > 1:
                import dataclasses as _dc

                from .batching import ContinuousBatchingEngine
                tier_eff = self.tier
                if (self.tier.draft_preset
                        and self.tier.temperature <= 0
                        and self.tier.spec_decode is None):
                    # AUTO (the tri-state default): the draft is the
                    # operator's ask, so arm spec_decode on the engine's
                    # tier view (frozen dataclass — replaced copy; the
                    # manager/client keep the configured tier).  An
                    # explicit spec_decode=False is the kill switch and
                    # passes through untouched.
                    tier_eff = _dc.replace(self.tier, spec_decode=True)
                engine = ContinuousBatchingEngine(
                    tier_eff, seed=self.seed, mesh=self.mesh,
                    devices=self.devices, params=params)
            else:
                engine = InferenceEngine(
                    self.tier, seed=self.seed, mesh=self.mesh,
                    devices=self.devices, params=params)
            if self.warmup_on_start:
                engine.warmup(beat=beat)  # dllm-lint: disable=lock-blocking-call -- lifecycle lock intentionally held through warmup; all liveness readers are lock-free (see docstring)
            # _started_at first: health() reads both lock-free, and an
            # engine visible before its timestamp would compute uptime
            # from None.
            self._started_at = time.time()
            self._engine = engine
            from ..ops.pallas_attention import kernel_mode
            logger.info("tier %s up in %.1fs (model=%s, devices=%s, "
                        "pallas kernels %s on backend %s)",
                        self.tier.name, time.perf_counter() - t0,
                        self.tier.model_preset,
                        [d.id for d in (self.devices or
                                        (mesh_devs(self.mesh) or [jax.devices()[0]]))],
                        kernel_mode(), jax.default_backend())

    def stop_server(self) -> None:
        """Drop the engine; params/KV buffers are freed with it."""
        with self._lock:
            stop = getattr(self._engine, "stop", None)
            if callable(stop):
                stop()                      # batching engine: join its loop
            self._engine = None
            self._started_at = None
            with self._wedged_lock:
                self._wedged_seen = False

    def drain(self, timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Graceful shutdown: stop admitting (the tier's admission gate
        rejects with the reference error shape + ``retry_after_s``;
        ``health()`` reports ``draining``), give in-flight requests up to
        ``timeout_s`` (default ``tier.drain_timeout_s``) to finish, then
        stop the engine — stragglers past the deadline fail with the
        engine-stopped error shape.

        MUST NOT be called under the lifecycle lock: it blocks for up to
        the deadline and then calls ``stop_server`` (which takes that
        lock) — the ``locks`` lint names ``drain`` a blocking call so the
        inversion can't be reintroduced.  Idempotent; returns a summary
        {draining_started, in_flight_at_start, drained, aborted,
        waited_s}."""
        timeout = (timeout_s if timeout_s is not None
                   else self.tier.drain_timeout_s)
        self._draining = True
        admission = getattr(self, "admission", None)
        if admission is not None:
            try:
                admission.start_drain(retry_after_s=timeout)
            except Exception:
                pass                         # stub controllers in tests
        t0 = time.monotonic()
        deadline = t0 + max(0.0, float(timeout))

        def in_flight() -> int:
            n = 0
            if admission is not None:
                try:
                    n = int(admission.snapshot().get("inflight", 0))
                except Exception:
                    n = 0
            engine = self._engine
            pending = getattr(engine, "pending_work", None)
            if callable(pending):
                try:
                    # The scheduler's view is sharper than admission's
                    # (it also counts directly-submitted work).
                    n = max(n, int(pending()))
                except Exception:
                    pass
            return n

        started = in_flight()
        while time.monotonic() < deadline and in_flight() > 0:
            time.sleep(0.02)
        leftover = in_flight()
        self.stop_server()
        drained = max(0, started - leftover)
        if drained:
            try:
                from ..obs import get_observability
                get_observability().m.drained_requests.labels(
                    self.tier.name).inc(drained)
            except Exception:
                pass
        if leftover:
            logger.warning("tier %s drain deadline (%.1fs) passed with %d "
                           "request(s) still in flight — stopped",
                           self.tier.name, timeout, leftover)
        else:
            logger.info("tier %s drained %d in-flight request(s) in %.2fs",
                        self.tier.name, drained, time.monotonic() - t0)
        return {"draining_started": True, "in_flight_at_start": started,
                "drained": drained, "aborted": leftover,
                "waited_s": round(time.monotonic() - t0, 3)}

    @property
    def draining(self) -> bool:
        return self._draining

    def is_server_running(self) -> bool:
        """LOCK-FREE: a single GIL-atomic attribute read.  Taking the
        lifecycle lock here would block every health probe through a
        multi-minute start_server compile and read as a dead tier (the
        PR 2 failure shape; the remote twin already reports lock-free,
        serving/tpu_api.py)."""
        return self._engine is not None

    def engine(self) -> InferenceEngine:
        """Lazy-start accessor (reference: Nano.process auto-start,
        src/models/nano.py:19-21).  Lock-free FAST path (the common
        case: engine already up); the cold-start slow path holds the
        lifecycle lock across check+start+read so a concurrent
        stop_server/restart can never make this return None or a
        just-stopped engine mid-handoff.  Only the probe surface
        (health/is_server_running) must never wait here — request
        dispatch waiting out a cold start is the correct behavior."""
        engine = self._engine
        if engine is not None:
            return engine
        with self._lock:
            if self._engine is None:
                self.start_server()  # dllm-lint: disable=lock-blocking-call -- cold-start serialization is exactly what the lifecycle lock is for; probes read lock-free, and a dispatcher must wait for the engine it asked for
            return self._engine

    # -- health (device-server GET /health surface) ------------------------

    def health(self) -> Dict[str, Any]:
        """Liveness + load snapshot (device-server GET /health surface).

        Beyond the reference's {"ok"}: the snapshot carries the tier's
        live load — admission queue depth, in-flight requests, batch
        slot occupancy — so queue-aware perf routing and the health
        allgather read one assembler (the TierClient registers its
        AdmissionController on ``self.admission``; batching engines
        expose ``queue_depth``/``slot_stats``)."""
        # LOCK-FREE on purpose: health() is the probe surface, and the
        # lifecycle lock is held through minutes of compile during a
        # (re)start — a probe waiting on it would read a merely-starting
        # tier as dead (PR 2; the remote /health twin already reports
        # lock-free).  start_server orders _started_at before _engine so
        # this unlocked snapshot never sees an engine without its
        # timestamp.
        engine = self._engine
        started_at = self._started_at
        running = engine is not None
        entry: Dict[str, Any] = {
            "ok": running,
            # Intentional shutdown in progress (or completed): probes and
            # the HealthMonitor must read this as policy, never failure.
            "draining": self._draining,
            "tier": self.tier.name,
            "model": self.tier.model_preset,
            "uptime_s": ((time.time() - started_at)
                         if running and started_at is not None else 0.0),
            "devices": ([d.id for d in self.mesh.devices.flat]
                        if self.mesh is not None else None),
        }
        # Load/occupancy counters are plain ints guarded by their own
        # locks (or GIL-safe reads).
        slots = getattr(engine, "slot_stats", None)
        if callable(slots):
            try:
                entry.update(slots())
            except Exception:
                pass
        # Decode watchdog (engine/batching.py progress_stall_s): a
        # scheduler with pending work but no completed progress past
        # tier.watchdog_stall_s is WEDGED — the round-5 failure mode.
        # health() flips unhealthy immediately so the HealthMonitor's
        # bounded restart fires on the next probe instead of waiting for
        # probe-count escalation.
        stall = getattr(engine, "progress_stall_s", None)
        if callable(stall):
            try:
                stall_s = float(stall())
            except Exception:
                stall_s = 0.0
            entry["decode_stall_s"] = round(stall_s, 3)
            deadline = self.tier.watchdog_stall_s
            if deadline is not None and stall_s > deadline:
                entry["ok"] = False
                entry["wedged"] = True
                entry["error"] = (f"decode watchdog: no step progress for "
                                  f"{stall_s:.1f}s (deadline "
                                  f"{deadline:.0f}s)")
                with self._wedged_lock:
                    rising = not self._wedged_seen
                    self._wedged_seen = True
                if rising:
                    # Rising edge only: the wedge COUNT must mean "times
                    # this engine wedged", not "times health() looked".
                    # The manager has no injection path, so this lands
                    # in the process-global registry (obs/__init__.py).
                    try:
                        from ..obs import get_observability
                        get_observability().m.watchdog_wedged.labels(
                            self.tier.name).inc()
                    except Exception:
                        pass
            else:
                with self._wedged_lock:
                    self._wedged_seen = False
        # Tick-forensics sideband (ISSUE 11): whether the engine's
        # profiler is live, how many ticks it has recorded, and the
        # recent phase-coverage fraction — GET /stats (which embeds
        # health()) shows at a glance whether /debug/trace will have
        # anything to say.  Advisory GIL-safe ring reads, no locks.
        prof = getattr(engine, "profiler", None)
        if prof is not None and getattr(prof, "enabled", False):
            try:
                entry["profile"] = prof.summary()
            except Exception:
                pass
        admission = getattr(self, "admission", None)
        if admission is not None:
            adm = admission.snapshot()
            entry["admission"] = adm
            # Top-level queue_depth = requests waiting beyond the
            # engine's concurrent slots (the perf strategy's signal);
            # engines without slot_stats get their occupancy inferred
            # from admission in-flight vs the tier's slot count.
            entry.setdefault("queue_depth", adm["queue_depth"])
            if "max_slots" not in entry:
                # The controller's slot count, not decode_batch: the
                # speculative fallback serves sequentially regardless
                # of the configured batch.
                slots_n = adm.get("slots") or max(1, self.tier.decode_batch)
                active = min(adm["inflight"], slots_n)
                entry["active_slots"] = active
                entry["max_slots"] = slots_n
                entry["slot_occupancy"] = round(active / slots_n, 3)
        elif "queue_depth" not in entry:
            entry["queue_depth"] = 0
        return entry


def mesh_devs(mesh):
    return list(mesh.devices.flat) if mesh is not None else None
