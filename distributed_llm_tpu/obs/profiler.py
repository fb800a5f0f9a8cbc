"""Tick-phase profiler: where each scheduler tick's milliseconds go.

PR 7's observability answers "what happened" (goodput, queues,
incidents); this module answers "why, and who pays".  A
``TickProfiler`` lives on each ``ContinuousBatchingEngine`` and records,
per scheduler pass, a structured breakdown of the tick into phases —
admission/slot bookkeeping (``admit``), the prefill/suffix-chunk device
calls inside an admission (``prefill``), COW boundary copies
(``cow_copy``), the work before the launch (``prepare``: speculative
plan, KV growth, the rng split, the ``pos``/``cur``/``temps`` uploads,
and nested in it the block-table upload ``table_upload``), the fused
decode tick (``decode``, whose children are ``dispatch`` — until the
jitted call returns — and ``fetch`` — the tick's one sanctioned device
sync), what follows the fetch (``account``: cost attribution and the
tick's counters), token fanout/detokenize (``emit``), and interleaved
chunk-prefill grants (``chunk_prefill``: a chunk's host work, the wait
for the chunk before it and its launch — between the tick's
``dispatch`` and ``fetch`` while slots decode, so nested in ``decode``
without being part of what the tick cost) — as a bounded ring of typed
tick records.  The scheduler's idle backoff (``idle_wait``) is an
annotation and a lifetime total only: it leaves no ring record, so an
idle engine does not flush its ring at 20 Hz.  Compile
events (``_note_compile``) and the justified admission-time host syncs
are stitched into the same timeline as instant events, so the
``retrace``/``transfer`` lint invariants get a dynamic counterpart: a
mid-serve compile or an unexpected sync shows up ON the timeline it
stalls.

Design constraints, in priority order:

- **Cheap when on.**  A phase stamp is two ``perf_counter`` calls and a
  list append on a stack the single scheduler thread owns — no locks,
  no allocation beyond the record tuples (the overhead pin in
  tests/test_profiler.py bounds the whole per-tick cost at ≤1% of the
  tiny-CPU tick p50).  Phase context managers are preallocated per
  name and reused; per-entry state lives on the profiler's stack, not
  the CM object.
- **Zero-cost when off.**  ``DLLM_PROFILE=0`` swaps in the shared
  ``NULL_PROFILER`` singleton: every stamp is a no-op method on a
  ``__slots__ = ()`` object returning a shared null context manager —
  the off path allocates nothing and records nothing, and the engine's
  attribution branch (gated on ``profiler.enabled``) never runs.
- **Never inside traced code.**  A ``perf_counter`` stamp inside a
  jit/pallas-traced function would bake one trace-time constant into
  the compiled program and measure nothing thereafter — the
  ``obs_discipline`` lint rule ``profiler-hook-in-traced-code``
  (lint/checkers/obs_discipline.py) statically forbids profiler calls
  anywhere in the project-wide traced closure.

**One timeline, the device's clock.**  Every stamp also enters and
exits a ``jax.profiler.TraceAnnotation`` named ``dllm.<tier>.<phase>``,
so any ``jax.profiler`` capture (the benchmark's, an operator's XProf
session) shows the scheduler's phases as host slices on the same
timeline and clock as the device ops.  With no capture running that is
one inactive TraceMe per stamp (0.3 us on this repo's CPU box).  There
is no second span system: the ring, the annotations, ``/debug/trace``
and the ``dllm_tick_phase_ms_total`` counters all read these stamps.

**Self-time vs duration.**  Phases nest (``prefill`` runs inside
``admit``); each recorded span carries both its full duration (what the
Chrome trace renders as a nested slice) and its SELF time (duration
minus children).  Self-times partition the tick wall, so the per-phase
p50/p95 table and the ≥95%-coverage acceptance check sum self-times —
never double-counting a parent and its child.

**Attribution.**  The engine divides each decode tick's device time
evenly across the slots it served and charges every slot's
``RequestTrace`` (``spans.charge``) with its ``device_time_ms`` share
plus ``kv_block_ticks`` — blocks held × ticks, each block weighted
1/refcount so a shared prefix block (PR 10) bills 1/k to each of its k
holders.  The router's exactly-once ``_finish_request`` exit aggregates
the totals per (tier, strategy, session) into the
``dllm_device_time_ms_total`` / ``dllm_kv_block_ticks_total`` metric
families and the bounded cost ledger ``GET /stats`` exposes — the
accounting substrate per-tenant quotas (ROADMAP item 4) and
goodput-per-replica-second economics (item 5) bill against.

Export: ``chrome_trace`` renders any set of per-tier profiler snapshots
as Chrome-trace/Perfetto JSON (``GET /debug/trace``, the bench profile
leg's artifact) — one synthetic thread per tier, ticks as enclosing
slices, phases as properly nested child slices, compile/host-sync
events as instants.  ``since``/``until`` (wall seconds) cut the ring to
a window after the fact, and the document's ``metadata`` gives the
origin of its ``ts`` axis on both ``time.time()`` and
``time.perf_counter()``, so a reader can place every slice on either
clock.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional

# Canonical phase names (DESIGN.md "Tick forensics").  The profiler
# accepts any name — this tuple is the documented set the engine stamps
# and the bench table orders by.  ``demote``/``promote`` (ISSUE 14) are
# the hierarchical-KV spill tier's dispatch costs: the async gather
# snapshot of an evicted prefix and the host→device write-back grants —
# the device↔host DRAIN itself lives on the copier thread and never
# stamps a tick phase.
PHASES = ("admit", "prefill", "cow_copy", "prepare", "table_upload",
          "decode", "dispatch", "fetch", "draft", "verify", "account",
          "emit", "chunk_prefill", "demote", "promote", "idle_wait")

# The names the sampler's timeline (``tick_phases``) and the
# ``dllm_tick_phase_p50_ms`` gauge carried before ``prepare``,
# ``dispatch``, ``fetch``, ``account`` and ``idle_wait`` existed.  They
# stay exactly these, so that what reads them (the benchmark's
# ``sched.host_ms_per_tick``) keeps its meaning; the newer phases are
# published through ``dllm_tick_phase_ms_total`` and ``/debug/trace``.
SAMPLED_PHASES = ("admit", "prefill", "cow_copy", "table_upload", "decode",
                  "draft", "verify", "emit", "chunk_prefill", "demote",
                  "promote")
# ``decode``'s time is all in its children now (``dispatch``,
# ``fetch``): wherever it is read as "what the decode tick cost" it is
# held to its own time plus theirs, not its emptied self-time.  Named,
# not "everything nested": a prefill chunk that rides between the two
# (``chunk_prefill``) nests in ``decode`` and is no part of the tick.
FULL_DURATION_PHASES = {"decode": ("decode", "dispatch", "fetch")}
_FULL_DURATION_OF = {part: whole
                     for whole, parts in FULL_DURATION_PHASES.items()
                     for part in parts}

# 120 s at 30 scheduler passes a second, rounded up: the benchmark
# reads the traced span out of the ring about 50 s after it happened.
# A record is a dict and about eight span tuples, 2.1 KB as measured on
# the tiny CPU engine: 8-9 MB an engine when full.
DEFAULT_CAPACITY = 4096
EVENT_CAPACITY = 512


class _NullPhase:
    """Shared no-op context manager for the disabled profiler."""

    __slots__ = ()

    def __enter__(self) -> "_NullPhase":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_PHASE = _NullPhase()


class NullProfiler:
    """The ``DLLM_PROFILE=0`` twin: every stamp is a no-op on a shared
    singleton — the off path allocates nothing per call (the overhead
    test pins ``phase()`` returning the same object every time)."""

    __slots__ = ()
    enabled = False

    def phase(self, name: str) -> _NullPhase:
        return _NULL_PHASE

    def idle_wait(self) -> _NullPhase:
        return _NULL_PHASE

    def event(self, name: str, **attrs: Any) -> None:
        pass

    def commit(self, slots: int = 0) -> None:
        pass

    def records(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        return []

    def events(self) -> List[Any]:
        return []

    def snapshot(self) -> Dict[str, Any]:
        return {"records": [], "events": []}

    def phase_stats(self, last: Optional[int] = None) -> Dict[str, Any]:
        return {"phases": {}, "coverage": None, "ticks": 0, "totals": {}}

    def summary(self) -> Dict[str, Any]:
        return {"enabled": False}


NULL_PROFILER = NullProfiler()


class _Phase:
    """Reusable per-name context manager: enter/exit delegate to the
    profiler's stack, so one object serves every occurrence of its
    phase (nesting state lives on the stack, not here)."""

    __slots__ = ("_prof", "_name", "_label")

    def __init__(self, prof: "TickProfiler", name: str):
        self._prof = prof
        self._name = name
        self._label = f"dllm.{prof.tier}.{name}"

    def __enter__(self) -> "_Phase":
        self._prof._push(self._name, self._label)
        return self

    def __exit__(self, *exc) -> None:
        self._prof._pop()
        return None


class _IdleWait:
    """The scheduler's idle backoff: an annotation and a lifetime
    total, never a ring record (it is not tick work, and the commit
    before the wait has already closed the pass)."""

    __slots__ = ("_prof", "_label", "_t0", "_ann")

    def __init__(self, prof: "TickProfiler"):
        self._prof = prof
        self._label = f"dllm.{prof.tier}.idle_wait"
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "_IdleWait":
        self._ann = self._prof._annotation(self._label)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        ms = (time.perf_counter() - self._t0) * 1000.0
        self._ann.__exit__(None, None, None)
        self._prof._add_total("idle_wait", ms, ms)
        return None


class TickProfiler:
    """Bounded ring of per-tick phase breakdowns for ONE engine.

    Single-writer discipline: only the scheduler thread stamps phases
    and commits records (same ownership model as ``_slots`` and the
    ``tick_ms`` ring); readers (``records``/``phase_stats``/``summary``,
    the sampler, ``GET /debug/trace``) take advisory GIL-safe snapshots
    with the same retry-don't-block policy as ``tick_stats``."""

    enabled = True

    def __init__(self, tier: str = "", capacity: int = DEFAULT_CAPACITY,
                 annotation=None):
        self.tier = tier
        self.capacity = max(16, int(capacity))
        # The host-span twin of every stamp: ``annotation(label)`` gives
        # a context manager (tests pass a recorder).  Imported here, not
        # at module level: ``obs`` stays importable without jax.
        if annotation is None:
            from jax.profiler import TraceAnnotation as annotation
        self._annotation = annotation
        self._ring: "deque[Dict[str, Any]]" = deque(maxlen=self.capacity)
        # Compile / host-sync instants, independent of tick records (a
        # warmup compile lands before any tick exists).  Own bounded
        # ring: (name, t_perf, attrs | None).
        self._events: "deque[tuple]" = deque(maxlen=EVENT_CAPACITY)
        self._cms: Dict[str, _Phase] = {}
        self._idle = _IdleWait(self)
        # Open-record state (scheduler thread only): phase stack entries
        # are [name, t0, child_seconds, annotation]; spans collect on
        # _pop.
        self._stack: List[List[Any]] = []
        self._spans: List[tuple] = []
        self._t0: Optional[float] = None
        self._seq = 0
        # Lifetime per-phase accumulators {name: [n, self_ms, dur_ms]} —
        # the attribution-conservation denominator and the
        # ``dllm_tick_phase_ms_total`` counters must cover EVERY tick
        # ever served, not just the ring's tail.
        self._totals: Dict[str, List[float]] = {}

    # -- stamping (scheduler thread) ---------------------------------------

    def phase(self, name: str) -> _Phase:
        cm = self._cms.get(name)
        if cm is None:
            cm = self._cms[name] = _Phase(self, name)
        return cm

    def idle_wait(self) -> _IdleWait:
        return self._idle

    def _push(self, name: str, label: str) -> None:
        ann = self._annotation(label)
        ann.__enter__()
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now
        self._stack.append([name, now, 0.0, ann])

    def _pop(self) -> None:
        name, t0, child_s, ann = self._stack.pop()
        now = time.perf_counter()
        ann.__exit__(None, None, None)
        dur_s = now - t0
        if self._stack:
            # The parent's self-time excludes this whole child.
            self._stack[-1][2] += dur_s
        self._spans.append((name, t0, dur_s, max(0.0, dur_s - child_s)))

    def event(self, name: str, **attrs: Any) -> None:
        """Instant event on the timeline (compile, sanctioned host
        sync).  Valid outside any tick — warmup compiles predate the
        first record."""
        self._events.append((name, time.perf_counter(),
                             attrs if attrs else None))

    def commit(self, slots: int = 0) -> None:
        """Close the open record (no-op when nothing was stamped this
        pass — idle loop passes leave no record)."""
        if self._t0 is None:
            return
        now = time.perf_counter()
        t0 = self._t0
        self._seq += 1
        spans = []
        for name, t, dur_s, self_s in self._spans:
            spans.append((name, (t - t0) * 1000.0, dur_s * 1000.0,
                          self_s * 1000.0))
            self._add_total(name, self_s * 1000.0, dur_s * 1000.0)
        self._ring.append({
            "seq": self._seq,
            "t0": t0,
            "dur_ms": (now - t0) * 1000.0,
            "slots": slots,
            "spans": spans,
        })
        self._t0 = None
        self._spans = []
        # A raise mid-phase can strand stack entries past the `with`
        # that owns them only if the CM protocol itself was bypassed;
        # clear defensively so one bad pass cannot skew every later one.
        self._stack.clear()

    def _add_total(self, name: str, self_ms: float, dur_ms: float) -> None:
        acc = self._totals.get(name)
        if acc is None:
            acc = self._totals[name] = [0, 0.0, 0.0]
        acc[0] += 1
        acc[1] += self_ms
        acc[2] += dur_ms

    # -- reads (any thread; advisory snapshots) ----------------------------

    def _snap_ring(self, ring) -> List[Any]:
        """GIL-safe deque copy with the tick_stats retry policy: a
        concurrent append can abort one iteration pass — retry, and
        report empty rather than block or raise."""
        for _ in range(3):
            try:
                return list(ring)
            except RuntimeError:
                continue
        return []

    def records(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        recs = self._snap_ring(self._ring)
        if last is not None and last > 0:
            recs = recs[-last:]
        return recs

    def events(self) -> List[tuple]:
        return self._snap_ring(self._events)

    def snapshot(self) -> Dict[str, Any]:
        """Everything the Chrome-trace export needs for this engine."""
        return {"records": self.records(), "events": self.events()}

    def phase_stats(self, last: Optional[int] = None) -> Dict[str, Any]:
        """Per-phase self-time quantiles over the ring's tail plus the
        lifetime totals and the coverage fraction (self-time sum / tick
        wall sum) — the bench profile leg's table and the ≥95% coverage
        acceptance check."""
        from .metrics import nearest_rank
        recs = self.records(last)
        per_phase: Dict[str, List[float]] = {}
        per_phase_dur: Dict[str, List[float]] = {}
        wall = 0.0
        covered = 0.0
        for rec in recs:
            wall += rec["dur_ms"]
            by_name: Dict[str, float] = {}
            dur_by_name: Dict[str, float] = {}
            for name, _rel, dur_ms, self_ms in rec["spans"]:
                by_name[name] = by_name.get(name, 0.0) + self_ms
                whole = _FULL_DURATION_OF.get(name)
                if whole is not None:
                    dur_by_name[whole] = dur_by_name.get(whole, 0.0) \
                        + self_ms
                covered += self_ms
            for name, ms in by_name.items():
                per_phase.setdefault(name, []).append(ms)
            for name, ms in dur_by_name.items():
                per_phase_dur.setdefault(name, []).append(ms)
        phases = {}
        for name, vals in per_phase.items():
            vals.sort()
            phases[name] = {
                "n": len(vals),
                "p50_ms": round(nearest_rank(vals, 0.5, presorted=True), 4),
                "p95_ms": round(nearest_rank(vals, 0.95, presorted=True), 4),
                "total_ms": round(sum(vals), 3),
            }
        for name, vals in per_phase_dur.items():
            phases[name]["dur_p50_ms"] = round(nearest_rank(vals, 0.5), 4)
        return {
            "phases": phases,
            "ticks": len(recs),
            "coverage": (round(covered / wall, 4) if wall > 0 else None),
            "totals": {name: {"n": int(acc[0]),
                              "total_ms": round(acc[1], 3)}
                       for name, acc in dict(self._totals).items()},
        }

    def sampled_phases(self, last: Optional[int] = None) -> Dict[str, Any]:
        """What the system-state sampler publishes (``tick_phases`` and
        the ``dllm_tick_phase_p50_ms`` gauge): the per-tick p50 of each
        ``SAMPLED_PHASES`` name — self-time, and for ``decode`` its full
        duration, which is what its self-time was before it had
        children — and the coverage fraction."""
        st = self.phase_stats(last)
        return {"tick_phases": {
                    name: entry.get("dur_p50_ms", entry["p50_ms"])
                    for name, entry in st["phases"].items()
                    if name in SAMPLED_PHASES},
                "coverage": st["coverage"]}

    def total_ms(self, phase: str) -> float:
        """Lifetime FULL-duration total for one phase, children
        included — for ``decode`` its own children only
        (``FULL_DURATION_PHASES``): the attribution-conservation
        denominator in tests, what the decode ticks cost."""
        parts = FULL_DURATION_PHASES.get(phase)
        if parts is not None:
            totals = dict(self._totals)
            return float(sum(totals[p][1] for p in parts if p in totals))
        acc = self._totals.get(phase)
        return float(acc[2]) if acc else 0.0

    def self_totals(self) -> Dict[str, float]:
        """Lifetime self-time milliseconds per phase — monotone; the
        source of ``dllm_tick_phase_ms_total``.  Self-times partition
        the scheduler's stamped time, so these add without counting a
        parent and its child twice."""
        return {name: float(acc[1])
                for name, acc in dict(self._totals).items()}

    def summary(self) -> Dict[str, Any]:
        """Cheap health()/GET /stats sideband: enabled flag, tick count,
        and coverage over the ring's recent tail."""
        st = self.phase_stats(last=64)
        return {"enabled": True, "ticks_recorded": self._seq,
                "ring": len(self._ring), "capacity": self.capacity,
                "coverage": st["coverage"]}


def make_profiler(tier: str = ""):
    """The engine's profiler, per the registered ``DLLM_PROFILE`` /
    ``DLLM_PROFILE_TICKS`` knobs: '0' → the shared zero-cost
    ``NULL_PROFILER``; anything else (default on) → a live ring."""
    from ..config_registry import env_int, env_str
    raw = (env_str("DLLM_PROFILE", "1") or "1").strip()
    if raw == "0":
        return NULL_PROFILER
    return TickProfiler(tier, capacity=env_int("DLLM_PROFILE_TICKS",
                                               DEFAULT_CAPACITY))


# =============================================================================
# Chrome-trace / Perfetto export
# =============================================================================

def chrome_trace(by_tier: Dict[str, Dict[str, Any]],
                 since: Optional[float] = None,
                 until: Optional[float] = None) -> Dict[str, Any]:
    """Render per-tier profiler snapshots (``TickProfiler.snapshot``)
    as Chrome-trace JSON (the ``chrome://tracing`` / Perfetto "JSON
    Array Format" with metadata): one pid, one synthetic thread per
    tier, each tick an enclosing ``X`` slice with its phases as nested
    child slices (full durations — nesting is the point; self-times
    ride in ``args``), compile/host-sync events as ``i`` instants.

    Timestamps are microseconds from the earliest stamp across ALL
    tiers (perf_counter is one process-wide monotonic clock, so
    cross-tier ordering is real).  Deterministic output ordering:
    tiers sorted by name, events by timestamp within a tier.

    ``since``/``until`` are wall-clock seconds (``time.time()``): only
    tick records that overlap the window, and instants inside it, are
    rendered.  ``metadata`` carries the origin of the ``ts`` axis on
    both clocks (``ts_origin_perf_counter_s``, ``ts_origin_unix_s``):
    a slice's ``perf_counter`` time is the origin plus ``ts`` / 1e6."""
    # perf_counter of a wall-clock second, as of this export.
    wall_minus_perf = time.time() - time.perf_counter()
    lo = None if since is None else float(since) - wall_minus_perf
    hi = None if until is None else float(until) - wall_minus_perf

    def keep(t_start: float, t_end: float) -> bool:
        return ((lo is None or t_end >= lo)
                and (hi is None or t_start <= hi))

    by_tier = {
        name: {"records": [r for r in snap.get("records", ())
                           if keep(r["t0"], r["t0"] + r["dur_ms"] / 1e3)],
               "events": [e for e in snap.get("events", ())
                          if keep(e[1], e[1])]}
        for name, snap in by_tier.items()}
    # Global time origin: earliest stamp anywhere, so every ts >= 0.
    origin: Optional[float] = None
    for snap in by_tier.values():
        for rec in snap["records"]:
            t = rec["t0"]
            origin = t if origin is None else min(origin, t)
        for ev in snap["events"]:
            t = ev[1]
            origin = t if origin is None else min(origin, t)
    if origin is None:
        origin = 0.0

    def us(t_perf: float) -> float:
        return round((t_perf - origin) * 1e6, 1)

    events: List[Dict[str, Any]] = []
    for tid, name in enumerate(sorted(by_tier), start=1):
        snap = by_tier[name]
        events.append({"name": "thread_name", "ph": "M", "pid": 1,
                       "tid": tid, "args": {"name": f"tier:{name}"}})
        for rec in snap.get("records", ()):
            t0 = rec["t0"]
            events.append({
                "name": "tick", "ph": "X", "pid": 1, "tid": tid,
                "ts": us(t0), "dur": round(rec["dur_ms"] * 1000.0, 1),
                "args": {"seq": rec["seq"], "slots": rec["slots"]},
            })
            for span in rec.get("spans", ()):
                pname, rel_ms, dur_ms, self_ms = span
                events.append({
                    "name": pname, "ph": "X", "pid": 1, "tid": tid,
                    "ts": us(t0 + rel_ms / 1000.0),
                    "dur": round(dur_ms * 1000.0, 1),
                    "args": {"self_ms": round(self_ms, 4)},
                })
        for ev in snap.get("events", ()):
            ename, t, attrs = ev[0], ev[1], (ev[2] if len(ev) > 2 else None)
            events.append({
                "name": ename, "ph": "i", "pid": 1, "tid": tid,
                "ts": us(t), "s": "t", "args": dict(attrs or {}),
            })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"ts_origin_perf_counter_s": origin,
                         "ts_origin_unix_s": origin + wall_minus_perf}}
