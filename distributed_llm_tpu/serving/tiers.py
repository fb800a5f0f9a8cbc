"""Tier clients — the device-client layer over in-process TPU engines.

Reference parity: src/models/nano.py / src/models/orin.py.  A TierClient has
the same surface (``.process(history)`` returning {"response": text} or an
error dict, plus ``.server_manager``) but dispatches to an InferenceEngine on
a chip submesh instead of POSTing through an SSH tunnel.  A registry replaces
the reference's two hard-coded classes, so tiers are config, not code.

Error-dict shapes match the reference client exactly (src/models/nano.py:
30-40) so Router failover and `_is_error` behave identically; faults come
from the injectable fault model (utils/faults.py) since there is no network
to fail naturally.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax

from ..config import ClusterConfig, TierConfig
from ..engine.inference import GenerationResult
from ..engine.manager import EngineManager
from ..obs import spans as obs_spans
from ..obs.spans import current_trace, use_trace
from ..parallel.mesh import carve_tier_meshes
from ..utils.faults import FaultInjector
from .tenants import DEFAULT_TENANT, TenantQuotas
from .turns import ClippedStream, clip_turn

logger = logging.getLogger(__name__)

History = Union[str, List[Dict[str, Any]]]

# Chars a fully-clipped stream may silently drain during _PrimedStream's
# eager first-delta pull before ClippedStream releases the primer with an
# empty delta: small enough that priming never stalls ~a whole
# generation, large enough that ordinary clipped turns finish their
# drain inside the prime.  WORST-CASE PRIME-DRAIN BOUND: a stream
# whose model emits a role marker from token one drains at most THIS many characters — ≈ PRIME_DRAIN_CHARS / 3.5 ≈ 74
# BPE tokens of decoding (~3.5 chars/token on the bench sets) — inside
# ``process_stream`` while holding a sequential engine's lock, before
# the "" sentinel releases the primer; without the cap the same prime
# blocked for the full max_new_tokens decode budget (48-128 tokens on
# the shipped clusters, up to 256 on the dataclass default).  See
# ClippedStream (serving/turns.py) for the mechanism.
PRIME_DRAIN_CHARS = 256


class AdmissionController:
    """Bounded per-tier admission with predictive fail-fast.

    The concurrency story for a batched tier is no longer a lock queue:
    requests admit freely up to the engine's ``decode_batch`` slots, and
    beyond that a bounded waiting line.  A request is REJECTED (reference
    error shape, so Router failover and the perf fail penalty fire) when
    either

    - the waiting line is full (``tier.admission_max_queue`` requests
      already waiting beyond the slots), or
    - the EWMA of recent service times predicts this request would wait
      past ``tier.request_timeout_s`` anyway — failing in microseconds
      what would otherwise fail by timeout after blocking a thread for
      the full cap, or
    - (``tier.kv_admission``, batched tiers) the request's PROJECTED KV
      block demand — prompt bucket + decode budget — exceeds the paged
      pool's free blocks plus the blocks reclaimable by evicting parked
      prefixes: a fixed HBM block pool admits by blocks, not slots, and
      a request that must starve should fail over now (reference error
      shape + ``retry_after_s``) instead of queuing forever, or
    - the tier is DRAINING (graceful shutdown, EngineManager.drain):
      rejection with ``retry_after_s`` so clients retry elsewhere/later.

    Composes with the abandoned-worker accounting: an abandoned
    timed-out call keeps its admission slot until the worker really
    finishes (the engine genuinely is busy with it), so a wedged tier's
    predicted wait grows and new traffic sheds to the healthy tier.
    """

    def __init__(self, tier: TierConfig, slots: Optional[int] = None):
        self.tier = tier
        # ``slots`` = the engine's REAL concurrency when the caller
        # knows it differs from decode_batch (the speculative fallback
        # serves sequentially) — admission believing in concurrency the
        # engine doesn't have would admit N× what can be served.
        self.slots = max(1, slots if slots is not None
                         else tier.decode_batch)
        self.max_queue = tier.admission_max_queue
        self.timeout_s = tier.request_timeout_s
        self._lock = threading.Lock()
        self._inflight = 0
        self._ewma_s: Optional[float] = None
        self._alpha = 0.25                    # EWMA smoothing
        self.admitted = 0
        self.rejected = 0
        self.kv_rejected = 0
        # Graceful drain (EngineManager.drain): while set, every request
        # is rejected with the drain reason; retry_after_s carries the
        # drain deadline as the client's retry hint.
        self._draining = False
        self._drain_retry_after: Optional[float] = None

    def try_admit(self, kv_demand: Optional[int] = None,
                  kv_supply: Optional[int] = None) -> Optional[str]:
        """None = admitted (caller MUST release exactly once); else the
        human-readable rejection reason.  ``kv_demand``/``kv_supply``
        (projected blocks needed vs free + reclaimable, from the tier's
        paged engine) arm the KV-pressure gate; either None skips it."""
        with self._lock:
            if self._draining:
                self.rejected += 1
                return "draining (graceful shutdown in progress)"
            waiting = max(0, self._inflight - self.slots)
            # The line this request would JOIN: cap 0 means "slots only,
            # nobody waits", not "reject even with free slots".
            waiting_after = max(0, self._inflight + 1 - self.slots)
            enabled = self.max_queue is not None   # None = control off
            if enabled and waiting_after > self.max_queue:
                self.rejected += 1
                return (f"queue full ({waiting} waiting, "
                        f"cap {self.max_queue})")
            if enabled and self.timeout_s is not None and self._ewma_s:
                # Queue wait only (queue_depth × EWMA / slots): a slow
                # request with a free slot is the per-request timeout's
                # job; admission rejects what would spend its whole
                # budget WAITING.
                predicted = (waiting / self.slots) * self._ewma_s
                if predicted > self.timeout_s:
                    self.rejected += 1
                    return (f"predicted queue wait {predicted:.1f}s "
                            f"exceeds the {self.timeout_s:.0f}s request "
                            f"timeout (queue_depth={waiting}, "
                            f"ewma_service={self._ewma_s:.2f}s)")
            if (kv_demand is not None and kv_supply is not None
                    and self._inflight < self.slots
                    and kv_demand > kv_supply):
                # A slot is FREE but the block pool cannot serve the
                # request (starvation / constrained pool) — the anomaly
                # this gate exists for: the request would sit in the
                # engine queue invisible to the wait predictor.  Shed
                # now, while the Router can still fail over.  At full
                # slot occupancy the gate stands down: blocks free when
                # slots finish, and the bounded queue + EWMA predictor
                # already model that wait in time units (shedding there
                # would reject saturated-load requests that queue fine).
                self.rejected += 1
                self.kv_rejected += 1
                return (f"projected KV demand {kv_demand} blocks exceeds "
                        f"{kv_supply} free+reclaimable (pool pressure)")
            self._inflight += 1
            self.admitted += 1
            return None

    # -- drain (EngineManager.drain) ---------------------------------------

    def start_drain(self, retry_after_s: Optional[float] = None) -> None:
        with self._lock:
            self._draining = True
            self._drain_retry_after = retry_after_s

    def end_drain(self) -> None:
        with self._lock:
            self._draining = False
            self._drain_retry_after = None

    @property
    def draining(self) -> bool:
        return self._draining

    def retry_after_s(self) -> float:
        """Client retry hint for a rejection: the drain deadline while
        draining, else the EWMA service time (one slot finishing frees
        capacity/blocks), else a 1 s floor."""
        with self._lock:
            if self._draining and self._drain_retry_after:
                return round(float(self._drain_retry_after), 2)
            if self._ewma_s:
                return max(0.1, round(self._ewma_s, 2))
        return 1.0

    def release(self, service_s: Optional[float] = None) -> None:
        """End of an admitted request.  ``service_s`` (wall time the
        engine was actually occupied — including timed-out calls, which
        are exactly the slow evidence the EWMA exists to capture) feeds
        the service-time estimate; pass None for requests that never
        reached the engine (injected faults, setup failures)."""
        with self._lock:
            self._inflight = max(0, self._inflight - 1)
            if service_s is not None and service_s >= 0:
                self._ewma_s = (service_s if self._ewma_s is None
                                else (1 - self._alpha) * self._ewma_s
                                + self._alpha * service_s)

    # -- observability -----------------------------------------------------

    def queue_depth(self) -> int:
        with self._lock:
            return max(0, self._inflight - self.slots)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            waiting = max(0, self._inflight - self.slots)
            return {
                "inflight": self._inflight,
                "queue_depth": waiting,
                "slots": self.slots,
                "max_queue": self.max_queue,
                "ewma_service_ms": (round(self._ewma_s * 1000.0, 2)
                                    if self._ewma_s is not None else None),
                "admitted": self.admitted,
                "rejected": self.rejected,
                "kv_rejected": self.kv_rejected,
                "draining": self._draining,
            }


class TierClient:
    def __init__(
        self,
        tier: TierConfig,
        manager: EngineManager,
        fault_injector: Optional[FaultInjector] = None,
    ):
        self.tier = tier
        self.name = tier.name
        self.server_manager = manager          # name matches reference surface
        self.faults = fault_injector
        self.last_result: Optional[GenerationResult] = None
        # Bounded admission replaces lock-serialization as the
        # concurrency story; registered on the manager so health()
        # snapshots expose queue depth next to slot occupancy.
        # Slot count mirrors EngineManager's engine choice.  A draft
        # with decode_batch>1 serves the BATCHED speculative path
        # (ISSUE 15 retired the PR 1 sequential fallback), so admission
        # believes in the real decode_batch slots; the only engine that
        # serves one stream — the sequential SpeculativeEngine — is
        # selected exactly when decode_batch<=1, where max(1, ...) is
        # already 1.
        slots = max(1, tier.decode_batch)
        self.admission = AdmissionController(tier, slots=slots)
        # Per-tenant quota layer (ISSUE 17) — constructed ONLY when the
        # tier opts in; ``tenant_quotas=None`` keeps every request on
        # the exact pre-tenant code path (byte-identity contract).
        self.tenants: Optional[TenantQuotas] = (
            TenantQuotas(tier) if tier.tenant_quotas is not None else None)
        try:
            manager.admission = self.admission
        except Exception:
            pass                               # stub managers in tests
        # Serializes the sequential engines once request timeouts can
        # abandon a still-running worker thread (engines without
        # ``concurrent_safe`` assume serialized callers); the batched
        # engine opts out via that attribute.
        self._engine_lock = threading.Lock()
        # Abandoned-worker accounting: while a timed-out worker is still
        # running (wedged chip), new sync requests on a serialized engine
        # would only queue behind it — fail them fast instead of growing
        # an unbounded daemon-thread backlog that drains serially on
        # recovery, each running a generation nobody reads.
        self._abandoned_lock = threading.Lock()
        self._abandoned = 0

    def process(self, history: History) -> Dict[str, Any]:
        """Run inference; error dicts mirror the reference client shapes.

        ``tier.request_timeout_s`` mirrors the reference clients' HTTP
        read timeout (src/models/nano.py:28, timeout=(5, 180)): the
        engine call runs in a worker thread, and past the cap this
        returns the reference error-dict shape — so Router failover and
        the perf strategy's failure penalty fire even though an
        in-process call on a wedged chip can never be cancelled.  The
        abandoned worker finishes (or hangs) in the background, exactly
        like the reference's Jetson finishing a response nobody waits
        for; its stale completion never overwrites ``last_result``.
        While an abandoned call is still outstanding on a serialized
        engine, new requests fail fast instead of spawning workers that
        would only queue behind the wedged call.

        Admission control runs FIRST (before fault injection, so a
        rejected request cannot consume a one-shot injected fault): a
        full waiting line or a predicted wait past the timeout returns
        the reference error shape in microseconds instead of blocking a
        serving thread for the full cap (AdmissionController)."""
        trace = current_trace()
        tenant = self._tenant_of(trace)
        # Tenant quota gate runs BEFORE the tier controller: a shed
        # over-quota tenant never consumes tier admission state (queue
        # slot, EWMA evidence, KV gate work) — the isolation property
        # the noisy-neighbor bench pins.  No-op when quotas are off.
        tenant_err = self._tenant_try_admit(trace, tenant)
        if tenant_err is not None:
            logger.warning("tier %s tenant quota rejected a request: %s",
                           self.name, tenant_err)
            return self._admission_error(tenant_err, tenant=tenant)

        def release_tenant():
            if self.tenants is not None:
                self.tenants.release(tenant)

        kv_demand, kv_supply = self._kv_admission_args(history)
        with obs_spans.span(trace, "admission", tier=self.name) as adm_sp:
            admit_err = self.admission.try_admit(kv_demand, kv_supply)
            if admit_err is not None:
                adm_sp.annotate(rejected=admit_err)
        if admit_err is not None:
            release_tenant()
            logger.warning("tier %s admission rejected a request: %s",
                           self.name, admit_err)
            return self._admission_error(admit_err)
        if self.faults is not None:
            fault = self.faults.intercept(self.name)
            if fault is not None:
                self.admission.release()     # never reached the engine
                release_tenant()
                return fault

        timeout = self.tier.request_timeout_s
        if timeout is None:
            t0 = time.perf_counter()
            try:
                resp, result = self._process_body(history)
            finally:
                self.admission.release(time.perf_counter() - t0)
                release_tenant()
            if result is not None:
                # Same lock as the timeout path's worker: last_result is
                # read/written cross-thread once timeouts can abandon
                # workers, so every rebind goes through _abandoned_lock
                # (the lock-mixed-guard lint pins this discipline).
                with self._abandoned_lock:
                    self.last_result = result
            return resp
        with self._abandoned_lock:
            abandoned_outstanding = self._abandoned
        if abandoned_outstanding and not self._engine_concurrent_safe():
            self.admission.release()
            release_tenant()
            logger.warning("tier %s has an abandoned timed-out call "
                           "outstanding — failing fast", self.name)
            return {"error": f"Request failed: {self.name} is busy with "
                             f"an abandoned timed-out request"}
        box: Dict[str, Any] = {}
        done = threading.Event()

        def work():
            resp: Dict[str, Any] = {"error": "Request failed: worker died"}
            result = None
            t0 = time.perf_counter()
            try:
                # Context vars don't cross thread spawns: re-bind the
                # request's trace so the engine's spans/timeline attach
                # to the right tree (obs/spans.py propagation contract).
                with use_trace(trace):
                    resp, result = self._process_body(history)
            finally:
                # Atomic with the caller's abandon decision: either
                # done is set HERE first (caller sees the result) or the
                # caller marked abandoned first (stale completion never
                # touches last_result).
                with self._abandoned_lock:
                    box["out"] = resp
                    done.set()
                    if box.get("abandoned"):
                        self._abandoned -= 1
                    elif result is not None:
                        self.last_result = result
                # The admission slot is held for the worker's whole
                # life — an abandoned worker still occupies the engine,
                # and its true duration is exactly the slow evidence
                # the EWMA should see.  Same lifetime for the tenant
                # quota slot: an abandoned worker still burns the
                # tenant's share of the engine.
                self.admission.release(time.perf_counter() - t0)
                release_tenant()

        threading.Thread(target=work, daemon=True,
                         name=f"{self.name}-request").start()
        if not done.wait(timeout):
            with self._abandoned_lock:
                if not done.is_set():
                    box["abandoned"] = True
                    self._abandoned += 1
            if box.get("abandoned"):
                logger.warning("tier %s request exceeded %.0fs — abandoning "
                               "the device call and reporting failure",
                               self.name, timeout)
                obs_spans.event(trace, "timeout_abandoned", tier=self.name,
                                timeout_s=timeout)
                return {"error": f"Request failed: {self.name} timed out "
                                 f"after {timeout:.0f}s"}
        return box.get("out", {"error": "Request failed: worker died"})

    def _kv_admission_args(self, history: History):
        """(projected block demand, available block supply) for the KV
        admission gate, or (None, None) when it doesn't apply: gate off,
        engine not running, or not a paged engine.  Peeks the live engine
        without lazy-starting it — a stopped tier's pool has no pressure
        to gate on."""
        if not self.tier.kv_admission:
            return None, None
        engine = getattr(self.server_manager, "_engine", None)
        demand_fn = getattr(engine, "projected_demand_blocks", None)
        stats_fn = getattr(engine, "kv_stats", None)
        if not (callable(demand_fn) and callable(stats_fn)):
            return None, None
        try:
            st = stats_fn()
            # reclaimable_blocks is pin- and refcount-aware (ISSUE 10):
            # parked entries with live sharers, and parked blocks whose
            # eviction would only drop one of several references, are
            # already excluded by the engine's PrefixCache — the gate
            # never promises supply that sharing has pinned.
            supply = (int(st["free_blocks"])
                      + int(st["reclaimable_blocks"])
                      # The in-flight chunked prefill's remaining block
                      # demand is spoken for: the allocator still counts
                      # those blocks free, but an admission that took
                      # them would force the scheduler to cancel the
                      # half-absorbed prompt (engine/batching.py
                      # kv_stats).
                      - int(st.get("prefill_pending_blocks", 0)))
            worst = getattr(engine, "max_demand_blocks", None)
            if callable(worst) and supply >= int(worst()):
                # Pool trivially covers ANY request: skip the per-request
                # prompt tokenization (the gate cannot fire) — the hot
                # path only pays the precise estimate under pressure.
                return None, None
            return int(demand_fn(history)), supply
        except Exception:
            return None, None               # estimation must never reject

    def _admission_error(self, admit_err: str,
                         tenant: Optional[str] = None) -> Dict[str, Any]:
        """Reference error shape for an admission rejection.  Drain and
        KV-pressure rejections carry the sanctioned ``retry_after_s``
        hint (serving/errors.py): both are transient-by-design states a
        client should retry past, unlike a full waiting line where
        failover is the productive move.  Tenant-quota rejections
        (ISSUE 17) always carry the hint, computed from the TENANT's
        own budget (token-bucket time-to-positive) rather than the
        tier EWMA — the tier may be idle while this tenant is shed."""
        from .errors import error_dict
        msg = (f"Request failed: {self.name} admission rejected: "
               f"{admit_err}")
        if (tenant is not None and self.tenants is not None
                and "tenant '" in admit_err):
            return error_dict(
                msg, retry_after_s=self.tenants.retry_after_s(tenant))
        if "draining" in admit_err or "KV demand" in admit_err:
            return error_dict(msg,
                              retry_after_s=self.admission.retry_after_s())
        return {"error": msg}

    def _tenant_of(self, trace) -> str:
        """The request's tenant identity, annotated onto the trace by
        the Router (serving/app.py validated it at the edge); requests
        arriving without one — direct TierClient callers, tests —
        bill to the shared default tenant."""
        try:
            t = trace.attrs.get("tenant") if trace is not None else None
        except Exception:
            t = None
        return t if isinstance(t, str) and t else DEFAULT_TENANT

    def _tenant_try_admit(self, trace, tenant: str) -> Optional[str]:
        """Quota-layer admission (None when quotas are off or the
        tenant is in budget; else the rejection reason).  The KV bill
        fed to the per-tenant block budget is the tenant's LIVE
        resident bill at 1/refcount from the engine — dedup lowers it,
        so a tenant whose prompts share prefixes is billed for its
        marginal footprint, not its nominal one."""
        if self.tenants is None:
            return None
        kv_bill = None
        if self.tenants.kv_budget(tenant) is not None:
            engine = getattr(self.server_manager, "_engine", None)
            bill_fn = getattr(engine, "tenant_kv_blocks", None)
            if callable(bill_fn):
                try:
                    kv_bill = bill_fn(tenant)
                except Exception:
                    kv_bill = None       # billing must never reject
        with obs_spans.span(trace, "tenant_admission", tier=self.name,
                            tenant=tenant) as t_sp:
            tenant_err = self.tenants.try_admit(tenant, kv_bill)
            if tenant_err is not None:
                t_sp.annotate(rejected=tenant_err)
        return tenant_err

    def _maybe_break_stream(self, handle):
        """Apply a scripted mid-stream kill (FaultInjector.
        fail_stream_after): the returned stream dies after N chunks —
        the wedge-after-first-token failure mode the Router's mid-stream
        failover exists for.  No kill scheduled → the handle unchanged."""
        from ..utils.faults import maybe_break_stream
        return maybe_break_stream(self.faults, self.name, handle)

    def _engine_concurrent_safe(self) -> bool:
        """Best-effort concurrent_safe probe: abandoned workers only
        serialize engines that assume serialized callers."""
        try:
            if self.server_manager.is_server_running():
                return getattr(self.server_manager.engine(),
                               "concurrent_safe", False)
        except Exception:
            pass
        return False

    def _process_body(self, history: History
                      ) -> Tuple[Dict[str, Any], Optional[GenerationResult]]:
        """Returns (response dict, result or None).  The CALLER owns the
        last_result update — on the timeout path it must be atomic with
        the abandon decision, so it cannot live here."""
        try:
            if not self.server_manager.is_server_running():
                logger.info("No running %s engine found, starting...", self.name)
                with obs_spans.span(current_trace(), "engine_start",
                                    tier=self.name):
                    self.server_manager.start_server()
            engine = self.server_manager.engine()
            if getattr(engine, "concurrent_safe", False):
                result = engine.generate(history)
            else:
                with self._engine_lock:
                    result = engine.generate(history)  # dllm-lint: disable=lock-blocking-call -- the engine lock IS the queue: sequential engines require serialized callers, and admission + request_timeout_s bound the wait
        except Exception as exc:   # engine failure → reference error shape
            # Engine-stopped failures (shutdown/drain deadline) carry the
            # schema-validated shape already — forward it verbatim.
            shape = getattr(exc, "shape", None)
            if isinstance(shape, dict) and "error" in shape:
                return dict(shape), None
            return {"error": f"Request failed: {exc}"}, None

        if result is None:
            # A stopped/abandoned request can complete with neither a
            # result nor an error (engine shut down mid-flight) — report
            # the reference error shape instead of crashing the worker.
            return {"error": f"Request failed: {self.name} engine "
                             f"returned no result"}, None
        # Single-turn semantic: the corpus-trained LM continues the
        # transcript past its own turn; the serving layer clips it
        # (serving/turns.py — the reference gets this from Ollama's
        # instruction-tuned models).  Per-request timing rides in the
        # raw dict (additive keys; _extract_text/_is_error only read
        # "response"/"error"): under concurrent clients the shared
        # ``last_result`` can belong to another request, so this is the
        # only race-free per-request TTFT a caller can observe.
        resp: Dict[str, Any] = {"response": clip_turn(result.text)}
        for key in ("ttft_ms", "total_ms", "gen_tokens"):
            val = getattr(result, key, None)   # stub results may omit these
            if val is not None:
                resp[key] = round(val, 3) if isinstance(val, float) else val
        return resp, result

    def process_stream(self, history: History):
        """Streaming twin of ``process``: returns a primed stream handle,
        or the reference error-dict shape on any setup failure.  Fault
        injection applies exactly like the sync path, and the stream is
        PRIMED (first token pulled, i.e. prefill has run) before this
        returns — engine errors are lazy, surfacing at first iteration,
        so priming is what makes setup-time failover able to catch real
        engine failures, not just injected ones.

        No per-token timeout here (unlike ``process``): a stream is
        consumed incrementally by the caller, so there is no single
        bounded wait to cap — a wedged chip stalls the SSE consumer,
        which owns its own disconnect policy.  Sequential engines DO
        take the tier lock for the stream's whole life (released on
        exhaustion, close, or GC): a timeout-abandoned sync worker must
        not interleave with a stream on an engine that assumes
        serialized callers.  The lock ACQUIRE is bounded by
        ``request_timeout_s`` though: if an abandoned worker (wedged
        chip) or a stalled live stream holds it, this returns the
        reference error shape so Router stream failover and the perf
        failure penalty fire instead of the serving thread hanging
        forever before priming.

        Streams occupy engine capacity like sync requests, so admission
        control gates them the same way; the admission slot is released
        exactly once when the stream finishes (exhaustion, close, or GC
        of an unconsumed handle).  Holding the slot until the CONSUMER
        drains is deliberate backpressure — slow SSE clients bound how
        many streams a tier buffers — but the EWMA service time uses the
        ENGINE-TRUE generation time from the final result when available
        (wall drain time is dominated by client read pace, and feeding
        it to the EWMA would let slow readers poison the predictive
        fail-fast against an idle engine)."""
        trace = current_trace()
        tenant = self._tenant_of(trace)
        tenant_err = self._tenant_try_admit(trace, tenant)
        if tenant_err is not None:
            logger.warning("tier %s tenant quota rejected a stream: %s",
                           self.name, tenant_err)
            return self._admission_error(tenant_err, tenant=tenant)

        def release_tenant():
            if self.tenants is not None:
                self.tenants.release(tenant)

        kv_demand, kv_supply = self._kv_admission_args(history)
        with obs_spans.span(trace, "admission", tier=self.name) as adm_sp:
            admit_err = self.admission.try_admit(kv_demand, kv_supply)
            if admit_err is not None:
                adm_sp.annotate(rejected=admit_err)
        if admit_err is not None:
            release_tenant()
            logger.warning("tier %s admission rejected a stream: %s",
                           self.name, admit_err)
            return self._admission_error(admit_err)
        t0 = time.perf_counter()
        handle_box: Dict[str, Any] = {}

        def finish_admission():
            result = getattr(handle_box.get("handle"), "result", None)
            engine_ms = getattr(result, "total_ms", 0) if result else 0
            self.admission.release(engine_ms / 1000.0 if engine_ms
                                   else time.perf_counter() - t0)
            release_tenant()

        try:
            if self.faults is not None:
                fault = self.faults.intercept(self.name)
                if fault is not None:
                    self.admission.release()   # never reached the engine
                    release_tenant()
                    return fault
            if not self.server_manager.is_server_running():
                logger.info("No running %s engine found, starting...", self.name)
                with obs_spans.span(trace, "engine_start", tier=self.name):
                    self.server_manager.start_server()
            engine = self.server_manager.engine()
            if not hasattr(engine, "generate_stream"):
                self.admission.release()
                release_tenant()
                return {"error": "Request failed: engine does not support "
                                 "token streaming"}
            if getattr(engine, "concurrent_safe", False):
                clipped = ClippedStream(
                    engine.generate_stream(history),
                    prime_drain_chars=PRIME_DRAIN_CHARS)
                handle_box["handle"] = clipped
                return _PrimedStream(self._maybe_break_stream(clipped),
                                     release=finish_admission)
            timeout = self.tier.request_timeout_s
            # A sequential engine's lock IS its queue: the wait here is
            # the streaming twin of the batching engine's queue_wait.
            with obs_spans.span(trace, "engine_lock_wait", tier=self.name):
                # timeout=-1 is threading's own "block forever" sentinel,
                # so the two branches collapse to ONE acquire site.
                # dllm-lint: disable=thread-acquire-leak -- the STREAM owns this lock past the frame: release_all/_PrimedStream release it on exhaustion/close/GC, and the except-BaseException below releases on setup failure — a try/finally here would release while the stream is still decoding
                acquired = self._engine_lock.acquire(
                    timeout=timeout if timeout is not None else -1)
            if not acquired:
                self.admission.release()
                release_tenant()
                logger.warning("tier %s stream setup could not take the "
                               "engine lock within %.0fs — failing over",
                               self.name, timeout)
                return {"error": f"Request failed: {self.name} engine busy "
                                 f"after {timeout:.0f}s"}

            def release_all():
                self._engine_lock.release()
                finish_admission()

            try:
                clipped = ClippedStream(
                    engine.generate_stream(history),  # dllm-lint: disable=lock-blocking-call -- a sequential engine's stream must hold the engine lock for its whole life (released by _PrimedStream on exhaustion/close/GC); the acquire above is bounded by request_timeout_s
                    prime_drain_chars=PRIME_DRAIN_CHARS)
                handle_box["handle"] = clipped
                return _PrimedStream(self._maybe_break_stream(clipped),
                                     release=release_all)
            except BaseException:
                self._engine_lock.release()
                raise
        except Exception as exc:
            self.admission.release()
            release_tenant()
            shape = getattr(exc, "shape", None)
            if isinstance(shape, dict) and "error" in shape:
                return dict(shape)         # engine-stopped: exact shape
            return {"error": f"Request failed: {exc}"}

    def load_snapshot(self) -> Dict[str, Any]:
        """Live load signal for queue-aware perf routing: requests
        waiting beyond the engine's concurrent slots, plus slot
        occupancy.  Never starts an engine (a stopped tier reads idle);
        cheap in-memory counters only."""
        adm = self.admission.snapshot()
        out = {"queue_depth": adm["queue_depth"],
               "active_slots": min(adm["inflight"], adm["slots"]),
               "max_slots": adm["slots"]}
        engine = getattr(self.server_manager, "_engine", None)
        slots = getattr(engine, "slot_stats", None)
        if callable(slots):
            try:
                st = slots()
                # The scheduler's view is sharper than admission's: its
                # queue counts submitted-not-admitted requests.
                out["queue_depth"] = max(out["queue_depth"],
                                         st["queue_depth"])
                out["active_slots"] = st["active_slots"]
                out["max_slots"] = st["max_slots"]
            except Exception:
                pass
        return out


class _PrimedStream:
    """A stream handle whose first delta has already been pulled (raising
    setup/prefill errors eagerly); iteration replays it then continues.

    ``release`` (the tier's engine-lock release) is invoked exactly once
    when the stream finishes — normal exhaustion, generator close (an
    SSE client disconnect closes the response generator chain), or GC of
    an unconsumed handle."""

    def __init__(self, handle, release=None):
        self._release_fn = release
        self._handle = handle
        self._it = iter(handle)
        self._first: Optional[str] = None
        self._exhausted = False
        try:
            self._first = next(self._it)
            if self._first == "":
                # ClippedStream's prime-release sentinel (a fully-
                # clipped stream capping its silent drain): the prime
                # succeeded, but there is no real first delta to replay.
                self._first = None
        except StopIteration:
            self._exhausted = True
        except BaseException:
            # Setup failure: the CALLER still holds (and releases) the
            # lock — neutralize ours so __del__ of this half-built
            # object can't double-release.
            self._release_fn = None
            raise

    def _release_once(self) -> None:
        fn, self._release_fn = self._release_fn, None
        if fn is not None:
            fn()

    def __iter__(self):
        try:
            if self._first is not None:
                yield self._first
            if not self._exhausted:
                yield from self._it
        finally:
            self._release_once()

    def __del__(self):
        self._release_once()

    @property
    def result(self):
        return self._handle.result


def build_tiers(
    cluster: ClusterConfig,
    devices: Optional[Sequence[jax.Device]] = None,
    fault_injector: Optional[FaultInjector] = None,
    warmup_on_start: bool = True,
) -> Dict[str, TierClient]:
    """Carve submeshes and wire a client per tier (registry, not classes).
    Tiers with an ``endpoint`` dispatch across hosts (serving/remote.py)
    instead of building a local engine."""
    meshes = carve_tier_meshes(cluster, devices=devices)
    tiers: Dict[str, TierClient] = {}
    for tier in cluster.tiers():
        if tier.endpoint:
            from .remote import RemoteTierClient
            tiers[tier.name] = RemoteTierClient(
                tier.name, tier.endpoint, fault_injector=fault_injector,
                spawn_cmd=tier.spawn_cmd)
            continue
        mesh = meshes[tier.name]
        if tier.replicas > 1 or tier.autoscale:
            # Replicated tier (ISSUE 12, serving/replicas.py): N engine
            # replicas behind one tier client with prefix-affinity
            # dispatch.  An autoscale-armed tier takes this path even
            # at replicas=1 — elastic membership (ISSUE 18) needs the
            # replica layer to actuate, and min may be 1.  Plain
            # replicas=1 WITHOUT autoscale never takes it — the
            # TierClient below stays byte-identical to pre-replica
            # behavior.
            from .replicas import ReplicatedTierClient
            tiers[tier.name] = ReplicatedTierClient(
                tier, cluster, mesh=mesh, fault_injector=fault_injector,
                warmup_on_start=warmup_on_start, seed=cluster.seed)
            continue
        # A 1-device mesh adds partitioning overhead for no benefit: pin to
        # the single device instead.
        if mesh.size == 1:
            manager = EngineManager(
                tier, devices=list(mesh.devices.flat), seed=cluster.seed,
                warmup_on_start=warmup_on_start)
        else:
            manager = EngineManager(
                tier, mesh=mesh, seed=cluster.seed,
                warmup_on_start=warmup_on_start)
        tiers[tier.name] = TierClient(tier, manager, fault_injector)
    return tiers
