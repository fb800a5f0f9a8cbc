"""Readers over the program's own host timeline: the tick profiler's
phase slices (``GET /debug/trace?since=..&until=..``, one source of
host spans, stamped in ``engine/batching.py``'s scheduler loop) put on
the device trace's clock, and the exact counters the program keeps
where the work happens (``/metrics`` before and after the run).

**The clock.**  A device trace counts nanoseconds from when the capture
was started; the program's slices are on ``time.perf_counter``, which
the benchmark shares (same process).  ``layers.Context.host_time``
places a trace stamp on ``perf_counter`` only coarsely (it is off by
however long ``start_trace`` took, 50 ms and more).  ``align`` makes
it exact by enclosure: every ``jit_decode_tick`` execution runs inside
one ``decode`` slice, and ends before that slice does by the lag of the
fetch; the offset is the one that makes the smallest such lag zero.
That leaves the offset early by the smallest fetch lag of the span
(tens of microseconds), which moves that much idle time from ``fetch``
to ``account``.

Everything returns None where the program has nothing to read: no
``metadata`` in ``/debug/trace`` and no ``jit_decode_tick`` (the commits
before PR 26), a ring that no longer holds the traced span, or fewer
than 95 % of the decode executions inside their slice.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from layer_metrics import named_readers
import tracing

# Phases in which the scheduler thread waits for the device (or, for
# ``idle_wait``, for work): the rest of a pass is host work with the
# device idle or running ahead.
DEVICE_WAIT_PHASES = ("fetch", "prefill", "chunk_prefill", "cow_copy",
                      "draft", "verify", "idle_wait")

# Which phases' self-time an idle interval is put down to, by metric.
# ``prepare`` takes the block-table upload nested in it and the launch;
# ``admit`` everything an admission does.  Idle time under any other
# slice (``decode``'s own microseconds, ``chunk_prefill``, ``demote``,
# ``promote``) or under none counts as unattributed.
GAP_GROUPS: Dict[str, Tuple[str, ...]] = {
    "fetch": ("fetch",),
    "account": ("account",),
    "emit": ("emit",),
    "admit": ("admit", "prefill", "cow_copy"),
    "prepare": ("prepare", "table_upload", "dispatch"),
}

MIN_INSIDE = 0.95
PAD_S = 1.0               # the ring is read this far around the traced span

Interval = Tuple[float, float]


# -- pure functions (tests/test_span_readers.py) -------------------------------

def self_intervals(slices: Sequence[Tuple[str, float, float]]
                   ) -> List[Tuple[str, float, float]]:
    """From properly nested (name, start, end) slices of one thread to
    the disjoint intervals in which each slice is the innermost one:
    a leaf whole, a parent without its children.  ``tick`` slices only
    enclose; their own time is under no phase and is left out."""
    out: List[Tuple[str, float, float]] = []
    stack: List[List] = []            # [name, end, cursor]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor and name != "tick":
                out.append((name, cursor, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, end in sorted(slices, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack:
            pname, _pend, cursor = stack[-1]
            if start > cursor and pname != "tick":
                out.append((pname, cursor, start))
            stack[-1][2] = max(cursor, start)
        stack.append([name, end, start])
    close(float("inf"))
    return sorted(out, key=lambda s: s[1])


def align(execs: Sequence[Sequence[int]], slices: Sequence[Interval],
          coarse_offset_s: float, max_shift: int = 3
          ) -> Optional[Tuple[float, float]]:
    """The offset (seconds) to add to a trace stamp / 1e9 to get its
    ``perf_counter``, and the share of executions that then lie inside
    their slice; None under ``MIN_INSIDE``.

    ``execs``: [start_ns, dur_ns] of the decode-tick executions, in
    order; ``slices``: (start, end) of the ``decode`` slices on
    ``perf_counter``, in order; ``coarse_offset_s``: the offset as far
    as it is known (picks the slice the first execution is tried in,
    and its ``max_shift`` neighbours).  Executions and slices pair in
    order; the pairing and offset kept are those that put most
    executions inside their slice and, among those, leave the smallest
    median fetch lag (a pairing that is off by a tick shows as lags
    that differ wherever ticks are irregular)."""
    if not execs or not slices:
        return None
    first_mid = (execs[0][0] + execs[0][1] / 2) / 1e9 + coarse_offset_s
    j0 = min(range(len(slices)),
             key=lambda j: abs((slices[j][0] + slices[j][1]) / 2 - first_mid))
    best = None
    for shift in range(-max_shift, max_shift + 1):
        j = j0 + shift
        if j < 0 or j + len(execs) > len(slices):
            continue
        pairs = list(zip(execs, slices[j:j + len(execs)]))
        lags = [b - (s + d) / 1e9 for (s, d), (_a, b) in pairs]
        offset = min(lags)
        inside = sum(1 for (s, _d), (a, _b) in pairs
                     if s / 1e9 + offset >= a) / len(pairs)
        cand = (inside, -statistics.median(lags) + offset,
                -abs(offset - coarse_offset_s), offset)
        if best is None or cand > best:
            best = cand
    if best is None or best[0] < MIN_INSIDE:
        return None
    return best[3], best[0]


def split_idle(idle: Sequence[Interval],
               phases: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Length of the idle intervals under each phase name (``phases``:
    disjoint (name, start, end), sorted), and under none (key None)."""
    out: Dict[Optional[str], float] = {None: 0.0}
    i = 0
    for lo, hi in sorted(idle):
        covered = 0.0
        while i < len(phases) and phases[i][2] <= lo:
            i += 1
        k = i
        while k < len(phases) and phases[k][1] < hi:
            name, a, b = phases[k]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            k += 1
        out[None] += (hi - lo) - covered
    return out


def gap_table(idle: Sequence[Interval],
              phases: Sequence[Tuple[str, float, float]]
              ) -> Dict[str, float]:
    """Idle time by ``GAP_GROUPS`` metric, plus ``unattributed`` and the
    ``idle`` total; the groups and ``unattributed`` add up to ``idle``."""
    by_phase = split_idle(idle, phases)
    table = {group: sum(by_phase.get(p, 0.0) for p in names)
             for group, names in GAP_GROUPS.items()}
    table["idle"] = sum(by_phase.values())
    table["unattributed"] = table["idle"] - sum(
        table[g] for g in GAP_GROUPS)
    return table


def tier_slices(doc: Dict, tier: str) -> Optional[List[Tuple[str, float,
                                                              float]]]:
    """(name, start, end) on ``perf_counter`` of the tier's slices in a
    ``/debug/trace`` document, by the origin its metadata gives."""
    origin = (doc.get("metadata") or {}).get("ts_origin_perf_counter_s")
    events = doc.get("traceEvents") or []
    tid = next((e["tid"] for e in events if e.get("ph") == "M"
                and e.get("args", {}).get("name") == f"tier:{tier}"), None)
    if origin is None or tid is None:
        return None
    return [(e["name"], origin + e["ts"] / 1e6,
             origin + (e["ts"] + e["dur"]) / 1e6)
            for e in events if e.get("ph") == "X" and e["tid"] == tid]


def reduce_span(dev: Dict, slices: Sequence[Tuple[str, float, float]],
                t_lo: int, t_hi: int, coarse_offset_s: float
                ) -> Optional[Dict[str, float]]:
    """Gap table in seconds of one device's trace against the tier's
    host slices, with ``ticks`` (decode ticks that START in the span: a
    tick the capture cut at its start began before it) and ``inside``;
    None where the clocks cannot be aligned."""
    execs = named_readers.executions(dev, "decode_tick")
    decodes = sorted((a, b) for n, a, b in slices if n == "decode")
    got = align(execs, decodes, coarse_offset_s)
    if got is None:
        return None
    offset, inside = got
    idle = [(s / 1e9 + offset, (s + n) / 1e9 + offset)
            for s, n in tracing.gaps_ns(dev["ops"], t_lo, t_hi)]
    table = gap_table(idle, self_intervals(slices))
    started = sum(1 for s, _ in execs if s > t_lo + named_readers.EDGE_NS)
    table.update(ticks=float(started), inside=inside, offset_s=offset)
    return table


# -- readers -------------------------------------------------------------------

def _span(ctx, tier) -> Optional[Dict[str, float]]:
    """The traced span's gap table for the tier's first chip, computed
    once a run."""
    cache = ctx.__dict__.setdefault("_span_tables", {})
    if tier not in cache:
        cache[tier] = _read_span(ctx, tier)
    return cache[tier]


def _read_span(ctx, tier):
    devs = ctx.tier_traces(tier)
    if not devs or ctx.trace["t_hi"] <= ctx.trace["t_lo"]:
        return None
    lo, hi = ctx.host_span
    doc = ctx.served.get_json(
        f"/debug/trace?since={lo + ctx.wall_offset - PAD_S!r}"
        f"&until={hi + ctx.wall_offset + PAD_S!r}")
    slices = tier_slices(doc or {}, tier)
    ticks = [s for s in slices or () if s[0] == "tick"]
    if not ticks or min(a for _, a, _ in ticks) > lo:
        return None             # no host timeline, or the ring has moved on
    t_lo = ctx.trace["t_lo"]
    return reduce_span(devs[0], slices, t_lo, ctx.trace["t_hi"],
                       ctx.host_time(t_lo) - t_lo / 1e9)


def gap_ms_per_tick(ctx, tier, group):
    """Device-idle milliseconds per decode tick of the traced span that
    fall under the self-time of the group's phases."""
    table = _span(ctx, tier)
    if table is None or not table["ticks"]:
        return None
    return 1000.0 * table[group] / table["ticks"]


def gap_unattributed_share(ctx, tier):
    """Share of the traced span's device-idle time under none of the
    ``GAP_GROUPS`` phases."""
    table = _span(ctx, tier)
    if table is None or table["idle"] <= 0:
        return None
    return 100.0 * table["unattributed"] / table["idle"]


def _series(text: str, name: str) -> List[Tuple[Dict[str, str], float]]:
    """(labels, value) of every sample of one series in a ``/metrics``
    text (``name`` in full: a histogram's ``_sum`` or ``_count``)."""
    out = []
    for line in text.splitlines():
        head, _, value = line.rpartition(" ")
        if head != name and not head.startswith(name + "{"):
            continue
        labels = dict(part.split("=", 1) for part in
                      head[len(name):].strip("{}").split(",") if part)
        out.append(({k: v.strip('"') for k, v in labels.items()},
                    float(value)))
    return out


def _delta(ctx, name, **labels):
    def total(text):
        return sum(v for lab, v in _series(text, name)
                   if all(lab.get(k) == want for k, want in labels.items()))
    return total(ctx.metrics_after) - total(ctx.metrics_before)


def host_self_ms_per_tick(ctx, tier):
    """Host milliseconds of the scheduler per decode tick, exactly: the
    run's growth of ``dllm_tick_phase_ms_total`` (lifetime self-times,
    every pass counted at its own weight) over the phases that do not
    wait for the device, by the growth of ``dllm_decode_ticks_total``."""
    after = [lab["phase"] for lab, _ in
             _series(ctx.metrics_after, "dllm_tick_phase_ms_total")
             if lab.get("tier") == tier]
    ticks = _delta(ctx, "dllm_decode_ticks_total", tier=tier)
    if not after or ticks <= 0:
        return None
    return sum(_delta(ctx, "dllm_tick_phase_ms_total", tier=tier, phase=p)
               for p in set(after) if p not in DEVICE_WAIT_PHASES) / ticks


def histogram_mean(ctx, family, tier=None):
    """Mean of the observations a histogram took between the two
    ``/metrics`` reads: growth of its sum over growth of its count."""
    labels = {} if tier is None else {"tier": tier}
    n = _delta(ctx, family + "_count", **labels)
    if n <= 0:
        return None
    return _delta(ctx, family + "_sum", **labels) / n
