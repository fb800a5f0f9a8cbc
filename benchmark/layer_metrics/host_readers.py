"""Who holds the interpreter between two ticks: readers over the CPU
clock the tick profiler reads beside its wall clock
(``dllm_tick_phase_cpu_ms_total``), the scheduler thread's run-queue
wait (``dllm_sched_runqueue_wait_ms_total``), and the stream consumer
threads' awake slices (``dllm_edge_*`` and the ``edge:<tier>:<lane>``
threads of ``/debug/trace``).

The scheduler's host time a tick (``span_readers.host_self_ms_per_tick``)
is split here into the part its thread ran (``host_cpu``) and the part
it stood in a phase without running (``host_off_cpu``): the two add up
to it exactly, being sums over the same phases of the same stamps.

Everything returns None where the program has nothing to read: no such
counter in ``/metrics`` (the commits before PR 41, or a kernel without
``schedstat``), no edge lane in ``/debug/trace``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from layer_metrics import span_readers
import tracing

Interval = Tuple[float, float]

WALL = "dllm_tick_phase_ms_total"
CPU = "dllm_tick_phase_cpu_ms_total"


# -- counters ----------------------------------------------------------------

def _per_tick(ctx, tier, family, **labels) -> Optional[float]:
    """Growth of one counter over the run by the growth of
    ``dllm_decode_ticks_total``; None where the program has no such
    series for the tier."""
    have = any(lab.get("tier") == tier and all(
        lab.get(k) == v for k, v in labels.items())
        for lab, _ in span_readers._series(ctx.metrics_after, family))
    ticks = span_readers._delta(ctx, "dllm_decode_ticks_total", tier=tier)
    if not have or ticks <= 0:
        return None
    return span_readers._delta(ctx, family, tier=tier, **labels) / ticks


def _host_phases(ctx, tier) -> List[str]:
    """The phases ``host_self_ms_per_tick`` sums, where the program
    keeps the CPU clock beside them (else none)."""
    def phases(family):
        return {lab["phase"] for lab, _ in
                span_readers._series(ctx.metrics_after, family)
                if lab.get("tier") == tier}
    return sorted(p for p in phases(WALL) & phases(CPU)
                  if p not in span_readers.DEVICE_WAIT_PHASES)


def _host_sum(ctx, tier, family) -> Optional[float]:
    phases = _host_phases(ctx, tier)
    parts = [_per_tick(ctx, tier, family, phase=p) for p in phases]
    return sum(parts) if phases and None not in parts else None


def host_cpu_ms_per_tick(ctx, tier):
    """CPU milliseconds of the scheduler thread per decode tick in the
    phases that do not wait for the device: the work a change can
    shrink or move."""
    return _host_sum(ctx, tier, CPU)


def host_off_cpu_ms_per_tick(ctx, tier):
    """Milliseconds per decode tick the scheduler thread stood inside
    those phases without running (self wall minus self CPU): it waited
    for the interpreter or for a core, which only fewer wake-ups or
    fewer threads shorten."""
    wall, cpu = _host_sum(ctx, tier, WALL), _host_sum(ctx, tier, CPU)
    return None if cpu is None else wall - cpu


def phase_cpu_ms_per_tick(ctx, tier, phase):
    """CPU milliseconds per decode tick in one phase (``fetch``: the
    transfer's and the conversion's own work, so the idle time under
    ``fetch`` less this is the wake-up)."""
    return _per_tick(ctx, tier, CPU, phase=phase)


def runqueue_wait_ms_per_tick(ctx, tier):
    """Milliseconds per decode tick the scheduler thread was runnable
    with no core: the part of its off-CPU time that is the machine's."""
    return _per_tick(ctx, tier, "dllm_sched_runqueue_wait_ms_total")


def edge_awake_cpu_ms_per_tick(ctx, tier):
    """CPU milliseconds per decode tick of the stream consumer threads:
    interpreter time the scheduler thread could not have."""
    return _per_tick(ctx, tier, "dllm_edge_awake_ms_total", clock="cpu")


def tokens_per_wakeup(ctx, tier):
    """Tokens a consumer thread takes a wake-up: ``decode_steps_per_tick``
    where it wakes once a tick, near 1 where every ``put`` wakes it."""
    have = span_readers._series(ctx.metrics_after, "dllm_edge_wakeups_total")
    wakes = span_readers._delta(ctx, "dllm_edge_wakeups_total", tier=tier)
    if not have or wakes <= 0:
        return None
    return span_readers._delta(ctx, "dllm_edge_tokens_total",
                               tier=tier) / wakes


# -- the edge lanes on the device's clock -------------------------------------

def edge_lane_tids(doc: Dict, tier: str) -> set:
    """Thread ids of the tier's edge lanes in a ``/debug/trace``
    document."""
    return {e["tid"] for e in doc.get("traceEvents") or []
            if e.get("ph") == "M" and str(e.get("args", {}).get(
                "name", "")).startswith(f"edge:{tier}:")}


def edge_slices(doc: Dict, tier: str) -> Optional[List[Interval]]:
    """(start, end) on ``perf_counter`` of every awake slice of the
    tier's edge lanes in a ``/debug/trace`` document; None where it has
    no such lane."""
    origin = (doc.get("metadata") or {}).get("ts_origin_perf_counter_s")
    events = doc.get("traceEvents") or []
    tids = edge_lane_tids(doc, tier)
    if origin is None or not tids:
        return None
    return sorted((origin + e["ts"] / 1e6, origin + (e["ts"] + e["dur"]) / 1e6)
                  for e in events if e.get("ph") == "X" and e["tid"] in tids)


def merged(intervals: Sequence[Interval]) -> List[Interval]:
    """The union of intervals as disjoint sorted intervals."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Where two lists of disjoint sorted intervals overlap."""
    out, i, k = [], 0, 0
    while i < len(a) and k < len(b):
        lo, hi = max(a[i][0], b[k][0]), min(a[i][1], b[k][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[k][1]:
            i += 1
        else:
            k += 1
    return out


def awake_share(idle: Sequence[Interval],
                phases: Sequence[Tuple[str, float, float]],
                awake: Sequence[Interval]) -> Optional[float]:
    """Of the idle time under the phases of ``span_readers.GAP_GROUPS``
    (``phases``: disjoint (name, start, end), sorted), the share in %
    during which at least one ``awake`` interval is open."""
    def under_groups(intervals):
        table = span_readers.gap_table(intervals, phases)
        return sum(table[group] for group in span_readers.GAP_GROUPS)
    total = under_groups(idle)
    if total <= 0:
        return None
    return 100.0 * under_groups(intersect(merged(idle), merged(awake))) / total


def gap_edge_awake_share(ctx, tier):
    """Of the traced span's device-idle time that ``span_readers`` puts
    under its five gap groups, the share during which at least one of
    the tier's stream consumer threads was awake.  The edge slices come
    from the same ``/debug/trace`` window as the tier's and go onto the
    device's clock by the offset ``span_readers.align`` found from the
    ``decode`` slices (one process, one ``perf_counter``)."""
    table = span_readers._span(ctx, tier)
    if table is None:
        return None
    lo, hi = ctx.host_span
    doc = ctx.served.get_json(
        f"/debug/trace?since={lo + ctx.wall_offset - span_readers.PAD_S!r}"
        f"&until={hi + ctx.wall_offset + span_readers.PAD_S!r}") or {}
    awake = edge_slices(doc, tier)
    slices = span_readers.tier_slices(doc, tier)
    if awake is None or not slices:
        return None
    offset = table["offset_s"]
    idle = [(s / 1e9 + offset, (s + n) / 1e9 + offset) for s, n in
            tracing.gaps_ns(ctx.tier_traces(tier)[0]["ops"],
                            ctx.trace["t_lo"], ctx.trace["t_hi"])]
    return awake_share(idle, span_readers.self_intervals(slices), awake)
