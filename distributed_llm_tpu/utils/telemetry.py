"""Telemetry: the TPU equivalent of the reference's power subsystem.

The reference SSHes a jtop sampler onto each Jetson (src/tests/
logging_power.py: 1 Hz lines "<ts>: <total_mW>"), scp's the logs back, and
integrates power over each query's [start, end) window into mJ
(src/tests/routing_chatbot_tester.py:239-254).  Cloud TPU exposes no
per-query power, so the same *shape* of subsystem samples what the hardware
does expose — per-device HBM occupancy (``device.memory_stats()``) — at the
same 1 Hz cadence, writes the same "<ts>: <value>" log format, and offers
the same trapezoidal window integration.  The integral is bytes·s (an
occupancy proxy, NOT millijoules); CSV columns keep the reference schema
with this documented substitution (SURVEY.md §5.1).

Also here: a phase-timer used by the serving stack to attribute time to
tokenize/prefill/decode/detokenize.  (A ``jax.profiler`` capture needs no
helper: ``obs/profiler.py`` puts the scheduler's phases into any capture
as ``dllm.<tier>.<phase>`` annotations.)
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from datetime import datetime
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax


def device_memory_snapshot() -> List[Dict[str, Any]]:
    """Per-device memory stats (empty dict per device where unsupported,
    e.g. host CPU backends)."""
    out = []
    for dev in jax.devices():
        try:
            stats = dev.memory_stats() or {}
        except Exception:
            stats = {}
        out.append({
            "device": dev.id,
            "platform": dev.platform,
            "bytes_in_use": stats.get("bytes_in_use", 0),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
            "bytes_limit": stats.get("bytes_limit", 0),
        })
    return out


class PhaseTimer:
    """Accumulates wall-time per named phase across queries, plus the
    roofline work (FLOPs / HBM bytes / tokens, utils/roofline.py) the
    engines report for each device phase — so utilization = work / time
    falls out of one snapshot."""

    def __init__(self):
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self.work: Dict[str, Dict[str, float]] = defaultdict(
            lambda: defaultdict(float))
        # Work an engine computes only when asked: a callable giving
        # {phase: {counter: amount}}, merged in by ``work_summary`` (the
        # batched engine counts its decode ticks by shape and leaves the
        # roofline arithmetic off the tick).
        self.lazy_work: Optional[Callable[[], Dict[str, Dict[str, float]]]] \
            = None

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.totals[name] += time.perf_counter() - t0
            self.counts[name] += 1

    def add_time(self, name: str, seconds: float) -> None:
        """One occurrence of a phase the caller timed itself (the
        batched engine's decode tick already holds its own stamps)."""
        self.totals[name] += seconds
        self.counts[name] += 1

    def add_work(self, name: str, **amounts: float) -> None:
        """Accumulate work counters (flops, hbm_bytes, tokens) for a phase."""
        acc = self.work[name]
        for key, val in amounts.items():
            acc[key] += float(val)

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {name: {"total_s": round(self.totals[name], 4),
                       "count": self.counts[name],
                       "mean_ms": round(1000 * self.totals[name]
                                        / max(1, self.counts[name]), 3)}
                for name in self.totals}

    def work_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-phase accumulated work joined with its measured seconds."""
        work = {name: dict(acc) for name, acc in self.work.items() if acc}
        if self.lazy_work is not None:
            for name, amounts in self.lazy_work().items():
                acc = work.setdefault(name, {})
                for key, val in amounts.items():
                    acc[key] = acc.get(key, 0.0) + float(val)
        return {name: {**{k: round(v, 2) for k, v in acc.items()},
                       "seconds": round(self.totals.get(name, 0.0), 4)}
                for name, acc in work.items()}


def engine_stats(engine) -> Dict[str, Any]:
    """Per-engine observability snapshot behind GET /stats
    (serving/app.py: tier and replica entries alike).  Tolerates any engine type (remote tiers
    have none; batching/speculative engines expose different subsets)."""
    entry: Dict[str, Any] = {}
    if engine is None:
        return entry
    if getattr(engine, "phases", None) is not None:
        entry["phases"] = engine.phases.summary()
        work = engine.phases.work_summary()
        if work:
            entry["work"] = work
    if getattr(engine, "prefix_cache", None) is not None:
        entry["prefix_cache"] = engine.prefix_cache.stats()
    kv_fn = getattr(engine, "kv_stats", None)
    if callable(kv_fn):
        # Pool-pressure + sharing snapshot (ISSUE 10): free/reclaimable
        # supply as the admission gate sees it, plus shared/pinned block
        # counts and the dedup ratio — GET /stats shows WHAT the KV gate
        # is gating on, inspectable without a metrics scrape.
        try:
            entry["kv"] = kv_fn()
        except Exception:
            pass
    pool_fn = getattr(engine, "pool_stats", None)
    if callable(pool_fn):
        # The pool's arrays as the device holds them at rest.
        entry["pool"] = pool_fn()
    pf_fn = getattr(engine, "prefill_stats", None)
    if callable(pf_fn):
        # Chunked prefill: what is in flight, and of the chunks
        # dispatched so far how many rode behind an unfetched tick.
        entry["prefill"] = pf_fn()
    tick_fn = getattr(engine, "tick_stats", None)
    if callable(tick_fn):
        # The decode tick: recent latency, and how often its inputs
        # were already on the device when it was launched.
        entry["tick"] = tick_fn()
    moe_fn = getattr(engine, "moe_stats", None)
    moe = moe_fn() if callable(moe_fn) else None
    if moe:
        entry["moe"] = moe
    state_fn = getattr(engine, "state_stats", None)
    state = state_fn() if callable(state_fn) else None
    if state:
        # The hybrid family's recurrent rows beside the K/V blocks.
        entry["state"] = state
    form_fn = getattr(engine, "decode_attention_form", None)
    if callable(form_fn):
        entry["decode_attention"] = form_fn()
    if hasattr(engine, "acceptance_rate"):
        entry["speculative_acceptance_rate"] = round(
            engine.acceptance_rate, 4)
    return entry


class TierTelemetry:
    """1 Hz sampler of per-tier device memory, window-integrable.

    Mirrors the reference power logger's lifecycle: ``start()`` (SSH nohup
    equivalent), ``stop()``, ``save_log(tier, path)`` ("scp" equivalent,
    same "<ts>: <value>" line format), and ``energy_for_window`` with the
    v2 harness's trapezoidal accumulation semantics.
    """

    def __init__(self, tiers: Iterable[str], interval_s: float = 1.0,
                 tier_devices: Optional[Dict[str, List[int]]] = None):
        self.tiers = list(tiers)
        self.interval_s = interval_s
        # Without an explicit tier→device map, every tier reads all devices
        # (correct for the single-chip bench; multi-slice deployments pass
        # the carved submesh device ids).
        self.tier_devices = tier_devices or {}
        self.samples: Dict[str, List[Tuple[float, float]]] = {
            t: [] for t in self.tiers}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _sample_once(self) -> None:
        now = time.time()
        snap = device_memory_snapshot()
        by_id = {s["device"]: s for s in snap}
        for tier in self.tiers:
            ids = self.tier_devices.get(tier)
            rows = ([by_id[i] for i in ids if i in by_id]
                    if ids else snap)
            total = float(sum(r["bytes_in_use"] for r in rows))
            self.samples[tier].append((now, total))

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self._sample_once()
            except Exception:
                pass
            self._stop.wait(self.interval_s)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="tier-telemetry")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=2 * self.interval_s)
        self._thread = None

    def save_log(self, tier: str, path: str) -> None:
        """Write the reference power-log line format: "<unix_ts>: <value>"."""
        with open(path, "w") as f:
            for ts, val in self.samples.get(tier, []):
                f.write(f"{ts:.3f}: {val:.0f}\n")

    def energy_for_window(self, tier: str, start: datetime,
                          end: datetime) -> float:
        """Integrate the piecewise-linear sample trace over [start, end)
        (the v2 harness's mW·s accumulation, routing_chatbot_tester.py:
        239-254).  Units: <sample unit>·s.

        Unlike the reference (whose multi-second Jetson queries always
        spanned several 1 Hz samples), TPU queries can finish between two
        samples — so the trace is interpolated to the exact window edges,
        and a window inside one sampling interval still integrates a
        nonzero slice.
        """
        t0, t1 = start.timestamp(), end.timestamp()
        pts = self.samples.get(tier, [])
        if not pts or t1 <= t0:
            return 0.0

        def value_at(t: float) -> float:
            # Clamp outside the trace; linear interpolation inside.
            if t <= pts[0][0]:
                return pts[0][1]
            if t >= pts[-1][0]:
                return pts[-1][1]
            for (ta, va), (tb, vb) in zip(pts, pts[1:]):
                if ta <= t <= tb:
                    if tb == ta:
                        return va
                    return va + (vb - va) * (t - ta) / (tb - ta)
            return pts[-1][1]

        knots = ([(t0, value_at(t0))]
                 + [(ts, v) for ts, v in pts if t0 < ts < t1]
                 + [(t1, value_at(t1))])
        total = 0.0
        for (ta, va), (tb, vb) in zip(knots, knots[1:]):
            total += 0.5 * (va + vb) * (tb - ta)
        return total
