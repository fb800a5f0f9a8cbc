"""Readers over the program's own counters: ``/stats`` before and after
the window, its sampled timeline (``/stats?timeline=1``: one snapshot
every 250 ms), and ``/metrics``."""
from __future__ import annotations

# Tick phases in which the scheduler waits for the device; the rest of a
# tick is host work.
DEVICE_PHASES = ("decode", "prefill", "chunk_prefill", "draft", "verify",
                 "cow_copy")


def _samples(ctx, tier):
    lo = ctx.t0 + ctx.wall_offset
    hi = lo + ctx.seconds
    return [s["tiers"][tier] for s in ctx.stats_after.get("timeline", [])
            if lo <= s["ts"] < hi and tier in s.get("tiers", {})]


def slot_occupancy(ctx, tier):
    rows = [s for s in _samples(ctx, tier) if s.get("max_slots")]
    if not rows:
        return None
    return 100.0 * sum(s["active_slots"] / s["max_slots"]
                       for s in rows) / len(rows)


def host_ms_per_tick(ctx, tier):
    """Host milliseconds of a scheduler pass outside its device calls:
    the tick profiler's phases (host clock) without those that end in a
    fetch from the device, averaged over the window's samples."""
    rows = [s["tick_phases"] for s in _samples(ctx, tier)
            if s.get("tick_phases")]
    if not rows:
        return None
    return sum(sum(v for k, v in r.items() if k not in DEVICE_PHASES)
               for r in rows) / len(rows)


def kv_blocks_used_share_peak(ctx, tier):
    kv = ctx.stats_after["tiers"].get(tier, {}).get("kv")
    rows = [s for s in _samples(ctx, tier) if "kv_free_blocks" in s]
    if not kv or not rows:
        return None
    total = kv["total_blocks"]
    return 100.0 * max(total - s["kv_free_blocks"] for s in rows) / total


def _histogram(text, family, tier):
    out = {}
    for line in text.splitlines():
        for part in ("sum", "count"):
            if line.startswith(f'{family}_{part}{{tier="{tier}"'):
                out[part] = float(line.rsplit(" ", 1)[1])
    return out


def queue_wait_ms_mean(ctx, tier):
    """Mean submit-to-slot wait of the requests that finished between
    the two ``/metrics`` reads (``dllm_queue_wait_ms``)."""
    a = _histogram(ctx.metrics_before, "dllm_queue_wait_ms", tier)
    b = _histogram(ctx.metrics_after, "dllm_queue_wait_ms", tier)
    n = b.get("count", 0) - a.get("count", 0)
    if n <= 0:
        return None
    return (b.get("sum", 0) - a.get("sum", 0)) / n


def shed(ctx, tier):
    def rejected(stats):
        return stats["tiers"].get(tier, {}).get("admission", {}).get(
            "rejected")
    a, b = rejected(ctx.stats_before), rejected(ctx.stats_after)
    return None if a is None or b is None else float(b - a)
