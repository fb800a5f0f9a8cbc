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
decode tick (``decode``: from its launch to the return of its fetch,
put in by ``span_from`` since a tick may be dispatched ahead of the
fetch before it and then begins in the pass before its own; under it
``dispatch`` — until the jitted call returns — and ``fetch`` — the
tick's one sanctioned device sync), what follows the fetch (``account``: cost attribution and the
tick's counters), token fanout/detokenize (``emit``), and interleaved
chunk-prefill grants (``chunk_prefill``: a chunk's host work, the wait
for the chunk before it and its launch — between the tick's
``dispatch`` and ``fetch`` while slots decode, so under ``decode``
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

- **Cheap when on.**  A phase stamp is two ``perf_counter`` calls, at
  most two ``thread_time`` calls (stamps under 10 us apart share one:
  ``CPU_REUSE_S``; where the call is dear, one pass in
  ``CPU_PASS_EVERY`` makes them) and a list append on a stack the single
  scheduler thread owns — no locks, no allocation beyond the record
  tuples (the overhead pin in tests/test_profiler.py bounds the whole
  per-tick cost at ≤1% of the tiny-CPU tick p50).  Phase context
  managers are preallocated per name and reused; per-entry state lives
  on the profiler's stack, not the CM object.
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

**Wall beside CPU.**  Every stamp reads the thread's CPU clock
(``time.thread_time``) next to the wall clock, and a span carries its
SELF CPU milliseconds the way it carries its self wall time (its CPU
minus its children's).  Self wall − self CPU of a phase that makes no
blocking call (``emit``, ``account``, ``admit``, ``prepare``,
``table_upload``, ``dispatch``) is the time the scheduler thread stood
inside the phase without running: it waited for the interpreter's lock,
or for a core.  For ``fetch`` the CPU is the transfer's and the
conversion's own work.  Where a reading of that clock is a slow system
call (over ``CPU_SLOW_S``, tried when the profiler is made: the v5e
hosts' sandboxed kernel), one scheduler pass in ``CPU_PASS_EVERY`` reads
it and counts that many times in the lifetime totals, which are
estimates there; the spans and the record of a pass that was not read
carry None.  To tell the two waits apart, ``commit`` reads
the scheduler thread's run-queue wait (the second field of
``/proc/thread-self/schedstat``: runnable, no core) once a pass with one
``pread``; its growth since the commit before rides on the tick record
(``runq_ms``) and adds up to ``dllm_sched_runqueue_wait_ms_total``.
Where that file cannot be read the figure is absent and nothing else
changes.

**Edge lanes: who else holds the interpreter.**  The scheduler shares
the interpreter with one consumer thread a stream.  Each stamps its
AWAKE slices through an ``EdgeLane`` (``engine/batching.py``
``generate_stream.deltas``, the one place every streamed token crosses
to its consumer): a slice opens when a ``token_queue.get`` that had to
wait returns (the first when the consumer starts) and closes when the
thread next has to wait, so tokens already queued extend the open slice
(one slice a wake, not one a token).  A slice is ``(lane, request_id,
start, end, cpu_ms, tokens, lag_ms)`` on ``perf_counter``: ``cpu_ms``
from the consumer thread's ``thread_time``, read at most every
``EDGE_CPU_EVERY_S`` a stream: the slice that reads it carries the
thread's CPU since the reading before (its own and that of the slices
between, whose ``cpu_ms`` is None; a thread that waits in ``get`` uses
none), ``lag_ms`` from the ``add_token`` stamp of the slice's first
token to its start (how long a token that exists waited for its
stream's thread to run; None where the slice took no token).  Slices go
to a bounded ring the tier's profiler owns (packed into a ``bytes``
record, which the garbage collector does not track; a ``deque`` append,
no lock) and into a ``TraceAnnotation`` ``dllm.<tier>.edge_awake``; a lane
number is held by one live stream at a time, so the slices of a lane
never overlap.  A lane keeps its own totals (single writer); the
profiler sums live and retired lanes when read (``edge_totals``), which
feeds ``dllm_edge_awake_ms_total{clock}``, ``dllm_edge_wakeups_total``,
``dllm_edge_tokens_total`` and the ``dllm_edge_wake_lag_ms`` histogram.
The only lock is taken once when a stream opens its lane and once when
it ends.

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
events as instants, and after the tier threads one thread an edge lane
(``edge:<tier>:<lane>``) with its awake slices.  ``since``/``until``
(wall seconds) cut the rings to
a window after the fact, and the document's ``metadata`` gives the
origin of its ``ts`` axis on both ``time.time()`` and
``time.perf_counter()``, so a reader can place every slice on either
clock.
"""

from __future__ import annotations

import bisect
import os
import struct
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from .metrics import DEFAULT_BUCKETS_MS

# Canonical phase names (DESIGN.md "Tick forensics").  The profiler
# accepts any name — this tuple is the documented set the engine stamps
# and the bench table orders by.  ``demote``/``promote`` (ISSUE 14) are
# the hierarchical-KV spill tier's dispatch costs: the async gather
# snapshot of an evicted prefix and the host→device write-back grants —
# the device↔host DRAIN itself lives on the copier thread and never
# stamps a tick phase.
PHASES = ("admit", "prefill", "cow_copy", "prepare", "table_upload",
          "decode", "dispatch", "fetch", "draft", "verify", "account",
          "emit", "chunk_prefill", "first_token", "demote", "promote",
          "idle_wait")

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
# A record is a dict and about eight span tuples, 2.5 KB as measured on
# the tiny CPU engine: 10 MB an engine when full.
DEFAULT_CAPACITY = 4096
EVENT_CAPACITY = 512
# 120 s at 30 passes a second and 16 streams woken a pass, rounded up.
# A slice rests packed (``_SLICE``: 48 bytes in a ``bytes`` object of
# 81): 6 MB of host memory an engine when full, the ring's own 0.5 MB
# with it (a 50 s run of 8 streams fills a fifth of it).  Packed, not a
# tuple: a ``bytes`` object is nothing the garbage collector tracks, so
# 400 slices a second bring no collection forward (as tuples they
# doubled the generation-0 collections of a benchmark window and put a
# 115 ms generation-2 collection into ``wide-reasoning``'s: PERF.md §6,
# PR 41).  None rests as NaN.
EDGE_CAPACITY = 65536
_SLICE = struct.Struct("<iqdddid")
_NAN = float("nan")
SCHEDSTAT_PATH = "/proc/thread-self/schedstat"
# Two scheduler stamps less than this far apart on the wall clock share
# one reading of the CPU clock (the end of a phase and the start of the
# next, a parent's start and its first child's): ``thread_time`` is a
# system call, 0.3 us on Linux proper and 5.7 us in the sandboxed kernel
# of the v5e hosts (PERF.md §6, PR 41), and CPU time cannot grow faster
# than the wall, so a shared reading is off by less than this.
CPU_REUSE_S = 10e-6
# Where one reading of that clock is dearer than ``CPU_SLOW_S`` (tried
# when the profiler is made), the scheduler reads it on one pass in
# ``CPU_PASS_EVERY`` and counts what it reads that many times: ten
# readings a pass at about 10 us each cost ``decode-closed`` 0.4 % of a
# reply on the v5e hosts, and their clock moves in steps of 10 ms, so a
# span's CPU is a sample there whichever pass reads it (PERF.md §6,
# PR 41).  The lifetime totals are then estimates; a span and a record
# of a pass that was not read carry None.  Five: prime, so that passes
# which recur every 4 or 8 ticks (a riding chunk, a table rung) are
# read as often as the others.
CPU_SLOW_S = 2e-6
CPU_PASS_EVERY = 5
# A stream's consumer reads its CPU clock at the close of an awake slice
# only if its last reading is this old (and at the stream's end): one
# reading a second a stream in place of two a wake-up.  On the
# v5e hosts a reading costs 5.7 us, 17.7 us while eight other threads
# take one too, and two a wake-up on eight consumers cost the replies
# 1.2 % (PERF.md §6, PR 41).  Nothing is lost in the sum: a thread that
# waits in ``get`` uses no CPU, so the clock's growth between two
# readings is the CPU of the slices between them.
EDGE_CPU_EVERY_S = 1.0


class _NullPhase:
    """Shared no-op context manager for the disabled profiler."""

    __slots__ = ()

    def __enter__(self) -> "_NullPhase":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_PHASE = _NullPhase()


class _NullLane:
    """Shared no-op edge lane for the disabled profiler."""

    __slots__ = ()

    def open(self) -> None:
        pass

    def wake(self) -> None:
        pass

    def sleep(self, taken: int) -> None:
        pass

    def close(self, taken: int) -> None:
        pass


_NULL_LANE = _NullLane()


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

    def edge_lane(self, trace: Any = None) -> _NullLane:
        return _NULL_LANE

    def event(self, name: str, **attrs: Any) -> None:
        pass

    def span_from(self, name: str, t0: float) -> None:
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

    __slots__ = ("_prof", "_label", "_t0", "_c0", "_ann")

    def __init__(self, prof: "TickProfiler"):
        self._prof = prof
        self._label = f"dllm.{prof.tier}.idle_wait"
        self._t0 = 0.0
        self._c0 = 0.0
        self._ann = None

    def __enter__(self) -> "_IdleWait":
        self._ann = self._prof._annotation(self._label)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        cpu_ms = (time.thread_time() - self._c0) * 1000.0
        ms = (time.perf_counter() - self._t0) * 1000.0
        self._ann.__exit__(None, None, None)
        self._prof._add_total("idle_wait", ms, cpu_ms)
        return None


class EdgeLane:
    """One stream's consumer thread on the tier's timeline: its awake
    slices and their totals.  Single writer (whichever thread iterates
    the stream); the profiler reads the totals advisorily and takes its
    lock only in ``open`` and ``close``, once a stream each."""

    __slots__ = ("_prof", "_times", "_next", "_base", "request_id", "lane",
                 "wakeups", "tokens", "wall_ms", "cpu_ms", "lag_ms", "lag_n",
                 "lag_counts", "_t0", "_c0", "_c_at", "_lag", "_ann")

    def __init__(self, prof: "TickProfiler", trace: Any = None):
        self._prof = prof
        # The request's token timeline, and how far another tier had
        # written it before this stream (a mid-stream failover shares
        # the trace): token k of this stream is stamped at base + k.
        self._times = None if trace is None else trace.token_times
        self._base = 0 if trace is None else len(trace.token_times)
        self._next = 0                    # tokens taken in closed slices
        self.request_id = 0 if trace is None else trace.request_id
        self.lane = -1
        self.wakeups = 0
        self.tokens = 0
        self.wall_ms = 0.0
        self.cpu_ms = 0.0
        self.lag_ms = 0.0
        self.lag_n = 0
        self.lag_counts = [0] * (len(DEFAULT_BUCKETS_MS) + 1)
        self._t0: Optional[float] = None
        self._c0 = 0.0                    # the thread's CPU clock ...
        self._c_at = 0.0                  # ... as of this wall stamp
        self._lag: Optional[float] = None
        self._ann = None

    def open(self) -> None:
        """The stream's consumer has started, on the thread that will
        iterate it (whose CPU clock the lane reads): take a lane number
        (the lowest no live stream holds) and open the first slice: the
        thread is awake, takes what is queued already and closes the
        slice at its first wait."""
        self._prof._edge_open(self)
        self._c0 = time.thread_time()
        self.wake()
        self._c_at = self._t0

    def wake(self) -> None:
        """A ``get`` that had to wait has returned: the slice opens."""
        prof = self._prof
        self._ann = ann = prof._annotation(prof._edge_label)
        ann.__enter__()
        self._t0 = now = time.perf_counter()
        times, ix = self._times, self._base + self._next
        self._lag = ((now - times[ix]) * 1000.0
                     if times is not None and ix < len(times) else None)

    def sleep(self, taken: int) -> None:
        """The thread is about to wait again (``taken``: tokens this
        stream has taken so far): the open slice, if any, closes."""
        t0 = self._t0
        if t0 is None:
            return
        now = time.perf_counter()
        cpu_ms = None
        if now - self._c_at > EDGE_CPU_EVERY_S:
            c0, self._c0 = self._c0, time.thread_time()
            self._c_at = now
            cpu_ms = (self._c0 - c0) * 1000.0
            self.cpu_ms += cpu_ms
        self._ann.__exit__(None, None, None)
        self._t0 = None
        n = taken - self._next
        self._next = taken
        lag = self._lag if n > 0 else None    # woken by the end alone
        self.wakeups += 1
        self.tokens += n
        self.wall_ms += (now - t0) * 1000.0
        if lag is not None:
            self.lag_ms += lag
            self.lag_n += 1
            self.lag_counts[bisect.bisect_left(DEFAULT_BUCKETS_MS, lag)] += 1
        self._prof._edge_ring.append(_SLICE.pack(
            self.lane, self.request_id, t0, now,
            _NAN if cpu_ms is None else cpu_ms, n,
            _NAN if lag is None else lag))

    def close(self, taken: int) -> None:
        """The stream has ended (or was abandoned): close the open
        slice and hand the totals and the lane number back."""
        self._c_at = float("-inf")        # the last slice reads the clock
        self.sleep(taken)
        self._prof._edge_close(self)


class TickProfiler:
    """Bounded ring of per-tick phase breakdowns for ONE engine.

    Single-writer discipline: only the scheduler thread stamps phases
    and commits records (same ownership model as ``_slots`` and the
    ``tick_ms`` ring); readers (``records``/``phase_stats``/``summary``,
    the sampler, ``GET /debug/trace``) take advisory GIL-safe snapshots
    with the same retry-don't-block policy as ``tick_stats``."""

    enabled = True

    def __init__(self, tier: str = "", capacity: int = DEFAULT_CAPACITY,
                 annotation=None, cpu_every: Optional[int] = None):
        self.tier = tier
        self.capacity = max(16, int(capacity))
        # One pass in ``cpu_every`` reads the CPU clock (1 where a
        # reading is cheap: every pass).
        self.cpu_every = (_cpu_pass_stride() if cpu_every is None
                          else max(1, int(cpu_every)))
        self._cpu_on = True
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
        # are [name, t0, child_seconds, annotation, cpu0,
        # child_cpu_seconds]; spans collect on _pop.
        self._stack: List[List[Any]] = []
        self._spans: List[tuple] = []
        self._t0: Optional[float] = None
        self._c0 = 0.0
        self._cpu = 0.0                   # the last CPU reading ...
        self._cpu_wall = float("-inf")    # ... and the wall time it was at
        self._seq = 0
        # Lifetime per-phase accumulators {name: [n, self_ms,
        # self_cpu_ms]} — the attribution-conservation denominator and
        # the ``dllm_tick_phase_ms_total`` / ``_cpu_ms_total`` counters
        # must cover EVERY tick ever served, not just the ring's tail.
        self._totals: Dict[str, List[float]] = {}
        # The scheduler thread's run-queue wait: its schedstat file,
        # opened by that thread at its first commit (None: not tried
        # yet; False: cannot be read here), the last reading in ns, and
        # the growth seen so far in ms.
        self._schedstat: Any = None
        self._runq_last_ns: Optional[int] = None
        self._runq_ms = 0.0
        # Edge lanes: the awake slices' ring (appended to by consumer
        # threads, one packed record and one deque append a slice), the
        # lanes of live streams
        # by number, and what ended streams handed back.
        self._edge_label = f"dllm.{tier}.edge_awake"
        self._edge_ring: "deque[bytes]" = deque(maxlen=EDGE_CAPACITY)
        self._edge_lock = threading.Lock()
        self._edge_live: Dict[int, EdgeLane] = {}
        self._edge_retired = EdgeLane(self)

    # -- stamping (scheduler thread) ---------------------------------------

    def phase(self, name: str) -> _Phase:
        cm = self._cms.get(name)
        if cm is None:
            cm = self._cms[name] = _Phase(self, name)
        return cm

    def idle_wait(self) -> _IdleWait:
        return self._idle

    def edge_lane(self, trace: Any = None) -> EdgeLane:
        """A lane for one stream's consumer (``trace``: the request's
        ``RequestTrace`` or None).  Made where the stream is, before
        its request is submitted; opened by the thread that iterates
        it."""
        return EdgeLane(self, trace)

    def _cpu_at(self, now: float) -> float:
        """The scheduler thread's CPU clock as of the wall stamp
        ``now``: read, unless a reading that ended under
        ``CPU_REUSE_S`` ago is there (adjacent stamps share one; a
        reading is aged from its END, the call itself being most of
        the way to the next stamp where it is slow).  On a pass that
        does not read the clock, the last reading: every difference
        is 0."""
        if self._cpu_on and now - self._cpu_wall > CPU_REUSE_S:
            self._cpu = time.thread_time()
            self._cpu_wall = time.perf_counter()
        return self._cpu

    def _push(self, name: str, label: str) -> None:
        ann = self._annotation(label)
        ann.__enter__()
        now = time.perf_counter()
        cpu = self._cpu_at(now)
        if self._t0 is None:
            self._t0 = now
            self._c0 = cpu
        self._stack.append([name, now, 0.0, ann, cpu, 0.0])

    def _pop(self) -> None:
        name, t0, child_s, ann, c0, child_cpu_s = self._stack.pop()
        now = time.perf_counter()
        cpu_s = self._cpu_at(now) - c0
        ann.__exit__(None, None, None)
        dur_s = now - t0
        if self._stack:
            # The parent's self-time excludes this whole child, on both
            # clocks.
            parent = self._stack[-1]
            parent[2] += dur_s
            parent[5] += cpu_s
        self._spans.append((name, t0, dur_s, max(0.0, dur_s - child_s),
                            max(0.0, cpu_s - child_cpu_s)))

    def span_from(self, name: str, t0: float) -> None:
        """A span of the open record that began at ``t0`` (a
        ``perf_counter`` stamp, possibly in an earlier pass) and ends
        now, beside the stack's spans and outside their nesting: the
        engine's ``decode``, a tick from its launch to the return of
        its fetch, which overlaps the next tick's where that one was
        dispatched ahead of this fetch.  All of its time is that of the
        phases stamped under it: self-time 0 on both clocks, and no
        annotation (an annotation is entered and left where it
        stands)."""
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now
            self._c0 = self._cpu_at(now)
        self._spans.append((name, t0, now - t0, 0.0, 0.0))

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
        # A pass that read the CPU clock stands for ``cpu_every``
        # passes in the totals; one that did not carries None.
        on, times = self._cpu_on, self.cpu_every
        cpu_ms = (self._cpu_at(now) - self._c0) * 1000.0 if on else None
        t0 = self._t0
        self._seq += 1
        spans = []
        for name, t, dur_s, self_s, cpu_s in self._spans:
            spans.append((name, (t - t0) * 1000.0, dur_s * 1000.0,
                          self_s * 1000.0, cpu_s * 1000.0 if on else None))
            self._add_total(name, self_s * 1000.0, cpu_s * 1000.0 * times)
        self._ring.append({
            "seq": self._seq,
            "t0": t0,
            "dur_ms": (now - t0) * 1000.0,
            "cpu_ms": cpu_ms,
            "runq_ms": self._runqueue_growth_ms(),
            "slots": slots,
            "spans": spans,
        })
        self._t0 = None
        self._spans = []
        self._cpu_on = self._seq % times == 0     # the pass that follows
        # A raise mid-phase can strand stack entries past the `with`
        # that owns them only if the CM protocol itself was bypassed;
        # clear defensively so one bad pass cannot skew every later one.
        self._stack.clear()

    def _add_total(self, name: str, self_ms: float, cpu_ms: float) -> None:
        acc = self._totals.get(name)
        if acc is None:
            acc = self._totals[name] = [0, 0.0, 0.0]
        acc[0] += 1
        acc[1] += self_ms
        acc[2] += cpu_ms

    def _runqueue_growth_ms(self) -> Optional[float]:
        """How long the calling (scheduler) thread stood runnable with
        no core since the commit before, from the second field of its
        schedstat; None where the file cannot be read, and for the
        first reading of a thread.  One ``pread`` on a file the thread
        opened itself (``thread-self`` names whoever opens it)."""
        f = self._schedstat
        if f is False:
            return None
        try:
            if f is None:
                f = self._schedstat = open(SCHEDSTAT_PATH, "rb", buffering=0)
                self._runq_last_ns = None
            ns = int(os.pread(f.fileno(), 64, 0).split()[1])
        except ProcessLookupError:
            # The thread that opened it is gone (an engine restarted):
            # the next commit opens this thread's own.
            f.close()
            self._schedstat = None
            return None
        except (OSError, ValueError, IndexError):
            self._schedstat = False
            return None
        last, self._runq_last_ns = self._runq_last_ns, ns
        if last is None:
            return None
        growth = max(0, ns - last) / 1e6
        self._runq_ms += growth
        return growth

    # -- edge lanes (consumer threads) -------------------------------------

    def _edge_open(self, lane: EdgeLane) -> None:
        with self._edge_lock:
            n = 0
            while n in self._edge_live:
                n += 1
            lane.lane = n
            self._edge_live[n] = lane

    def _edge_close(self, lane: EdgeLane) -> None:
        with self._edge_lock:
            if self._edge_live.get(lane.lane) is not lane:
                return                    # never opened, or closed twice
            del self._edge_live[lane.lane]
            _fold_lane(self._edge_retired, lane)

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

    def edge_slices(self) -> List[tuple]:
        """The ring's awake slices, unpacked: ``(lane, request_id,
        start, end, cpu_ms, tokens, lag_ms)``."""
        out = []
        for raw in self._snap_ring(self._edge_ring):
            lane, rid, t0, t1, cpu_ms, n, lag = _SLICE.unpack(raw)
            out.append((lane, rid, t0, t1,
                        None if cpu_ms != cpu_ms else cpu_ms, n,
                        None if lag != lag else lag))
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Everything the Chrome-trace export needs for this engine."""
        return {"records": self.records(), "events": self.events(),
                "edge": self.edge_slices()}

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
            for name, _rel, dur_ms, self_ms, _cpu_ms in rec["spans"]:
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
                              "total_ms": round(acc[1], 3),
                              "cpu_ms": round(acc[2], 3)}
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
        """Lifetime total of one phase with the children
        ``FULL_DURATION_PHASES`` names for it (``decode``: its own time
        plus ``dispatch`` and ``fetch``, what the decode ticks cost):
        the attribution-conservation denominator in tests."""
        parts = FULL_DURATION_PHASES.get(phase, (phase,))
        totals = dict(self._totals)
        return float(sum(totals[p][1] for p in parts if p in totals))

    def self_totals(self) -> Dict[str, float]:
        """Lifetime self-time milliseconds per phase — monotone; the
        source of ``dllm_tick_phase_ms_total``.  Self-times partition
        the scheduler's stamped time, so these add without counting a
        parent and its child twice."""
        return {name: float(acc[1])
                for name, acc in dict(self._totals).items()}

    def cpu_totals(self) -> Dict[str, float]:
        """Lifetime self CPU milliseconds per phase, beside
        ``self_totals``: the source of ``dllm_tick_phase_cpu_ms_total``.
        A phase's self wall minus this is the time the scheduler thread
        stood in it without running."""
        return {name: float(acc[2])
                for name, acc in dict(self._totals).items()}

    def runqueue_wait_ms(self) -> Optional[float]:
        """Lifetime run-queue wait of the scheduler thread as far as
        the commits saw it grow (``dllm_sched_runqueue_wait_ms_total``);
        None where its schedstat cannot be read (or no commit read it
        yet)."""
        return self._runq_ms if self._schedstat else None

    def edge_totals(self) -> Dict[str, Any]:
        """Lifetime totals of the tier's edge lanes, ended streams and
        live ones: ``wakeups`` (slices), ``tokens``, ``wall_ms`` and
        ``cpu_ms`` awake, and the wake lag as ``lag_ms`` (sum),
        ``lag_n`` and ``lag_counts`` (over ``DEFAULT_BUCKETS_MS`` and
        +Inf).  Live lanes are read while their threads write them:
        each figure is monotone, two of them may be a slice apart."""
        total = EdgeLane(self)
        with self._edge_lock:
            _fold_lane(total, self._edge_retired)
            for lane in self._edge_live.values():
                _fold_lane(total, lane)
        return {key: getattr(total, key) for key in _LANE_TOTALS}

    def summary(self) -> Dict[str, Any]:
        """Cheap health()/GET /stats sideband: enabled flag, tick count,
        and coverage over the ring's recent tail."""
        st = self.phase_stats(last=64)
        return {"enabled": True, "ticks_recorded": self._seq,
                "ring": len(self._ring), "capacity": self.capacity,
                "coverage": st["coverage"]}


_LANE_TOTALS = ("wakeups", "tokens", "wall_ms", "cpu_ms", "lag_ms", "lag_n",
                "lag_counts")


def _fold_lane(into: EdgeLane, lane: EdgeLane) -> None:
    into.wakeups += lane.wakeups
    into.tokens += lane.tokens
    into.wall_ms += lane.wall_ms
    into.cpu_ms += lane.cpu_ms
    into.lag_ms += lane.lag_ms
    into.lag_n += lane.lag_n
    into.lag_counts = [a + b for a, b in zip(into.lag_counts,
                                             lane.lag_counts)]


def _cpu_pass_stride() -> int:
    """``CPU_PASS_EVERY`` where a reading of the thread's CPU clock is
    dearer than ``CPU_SLOW_S`` here (the cheapest of five tries, so a
    stall of the machine does not decide it), else 1."""
    cost = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        time.thread_time()
        cost = min(cost, time.perf_counter() - t0)
    return CPU_PASS_EVERY if cost > CPU_SLOW_S else 1


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
    child slices (full durations — nesting is the point; self wall and
    self CPU ride in ``args``), compile/host-sync events as ``i``
    instants.  After every tier's thread come the edge lanes, a thread
    ``edge:<tier>:<lane>`` each, with the stream consumers' awake slices
    (``edge_awake``; ``args``: ``request_id``, ``tokens``, where the
    slice read the clock ``cpu_ms`` since the reading before, where it
    took a token ``wake_lag_ms``, so the slice's first token was stamped
    at ``ts`` - 1000 * ``wake_lag_ms``): the tier threads' events are the
    same with lanes as without.

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
                          if keep(e[1], e[1])],
               "edge": [s for s in snap.get("edge", ())
                        if keep(s[2], s[3])]}
        for name, snap in by_tier.items()}
    # Global time origin: the earliest tick or instant anywhere, so
    # every ts of a tier thread is >= 0 (an edge slice that was open
    # when the first kept tick began starts before it); the earliest
    # edge slice where there is neither.
    # (a ``decode`` slice begins before its record where its tick was
    # dispatched in the pass before: ``span_from``.)
    stamps = [rec["t0"] + min([0.0] + [sp[1] for sp in rec["spans"]]) / 1e3
              for snap in by_tier.values() for rec in snap["records"]]
    stamps += [ev[1] for snap in by_tier.values() for ev in snap["events"]]
    if not stamps:
        stamps = [s[2] for snap in by_tier.values() for s in snap["edge"]]
    origin = min(stamps, default=0.0)

    def us(t_perf: float) -> float:
        return round((t_perf - origin) * 1e6, 1)

    events: List[Dict[str, Any]] = []
    for tid, name in enumerate(sorted(by_tier), start=1):
        snap = by_tier[name]
        events.append({"name": "thread_name", "ph": "M", "pid": 1,
                       "tid": tid, "args": {"name": f"tier:{name}"}})
        for rec in snap.get("records", ()):
            t0 = rec["t0"]
            args = {"seq": rec["seq"], "slots": rec["slots"]}
            for key in ("cpu_ms", "runq_ms"):
                if rec.get(key) is not None:
                    args[key] = round(rec[key], 4)
            events.append({
                "name": "tick", "ph": "X", "pid": 1, "tid": tid,
                "ts": us(t0), "dur": round(rec["dur_ms"] * 1000.0, 1),
                "args": args,
            })
            for pname, rel_ms, dur_ms, self_ms, cpu_ms in rec.get(
                    "spans", ()):
                events.append({
                    "name": pname, "ph": "X", "pid": 1, "tid": tid,
                    "ts": us(t0 + rel_ms / 1000.0),
                    "dur": round(dur_ms * 1000.0, 1),
                    "args": ({"self_ms": round(self_ms, 4)}
                             if cpu_ms is None else
                             {"self_ms": round(self_ms, 4),
                              "cpu_ms": round(cpu_ms, 4)}),
                })
        for ev in snap.get("events", ()):
            ename, t, attrs = ev[0], ev[1], (ev[2] if len(ev) > 2 else None)
            events.append({
                "name": ename, "ph": "i", "pid": 1, "tid": tid,
                "ts": us(t), "s": "t", "args": dict(attrs or {}),
            })
    tid = len(by_tier)
    for name in sorted(by_tier):
        by_lane: Dict[int, List[tuple]] = {}
        for s in by_tier[name]["edge"]:
            by_lane.setdefault(s[0], []).append(s)
        for lane in sorted(by_lane):
            tid += 1
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid,
                           "args": {"name": f"edge:{name}:{lane}"}})
            for _lane, request_id, t0, t1, cpu_ms, tokens, lag in sorted(
                    by_lane[lane], key=lambda s: s[2]):
                args = {"request_id": request_id, "tokens": tokens}
                if cpu_ms is not None:
                    args["cpu_ms"] = round(cpu_ms, 4)
                if lag is not None:
                    args["wake_lag_ms"] = round(lag, 4)
                events.append({
                    "name": "edge_awake", "ph": "X", "pid": 1, "tid": tid,
                    "ts": us(t0), "dur": round((t1 - t0) * 1e6, 1),
                    "args": args,
                })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"ts_origin_perf_counter_s": origin,
                         "ts_origin_unix_s": origin + wall_minus_perf}}
