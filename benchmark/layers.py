"""What a per-layer reader is given, and the device figures of the line."""
from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import manifest as mf
import tracing


@dataclasses.dataclass
class Context:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    served: Any                       # cluster.Served
    records: List[Dict[str, Any]]     # drive.py's request records
    t0: float                         # perf_counter at the window's start
    seconds: float
    wall_offset: float                # time.time() - time.perf_counter()
    stats_before: Dict[str, Any]      # GET /stats before the traffic
    stats_after: Dict[str, Any]       # GET /stats?timeline=1 after it
    metrics_before: str               # GET /metrics, both sides
    metrics_after: str
    trace: Dict[str, Any]             # tracing.load(...)
    host_span: Tuple[float, float]    # perf_counter around the capture
    peaks: Optional[Dict[str, float]]

    def tier_device_ids(self, tier: str) -> List[str]:
        return [str(d.id) for d in self.served.tier_devices(tier)]

    def tier_traces(self, tier: str) -> List[Dict[str, Any]]:
        """The loaded trace of each of the tier's chips; a chip on which
        nothing ran has empty lists."""
        devs = self.trace["devices"]
        return [devs.get(i) or devs.get(int(i)) or {"ops": [], "modules": []}
                for i in self.tier_device_ids(tier)]

    def host_time(self, trace_ns: int) -> float:
        """perf_counter of a trace timestamp, taking the capture's first
        and last device events for the two ends of the host span."""
        lo, hi = self.trace["t_lo"], self.trace["t_hi"]
        frac = (trace_ns - lo) / max(1, hi - lo)
        return self.host_span[0] + frac * (self.host_span[1]
                                           - self.host_span[0])


def load_peaks(device_kind: str, rehearse: bool
               ) -> Optional[Dict[str, float]]:
    table = mf.load_json("peaks.json")["peaks"]
    if device_kind in table:
        return table[device_kind]
    if rehearse:
        return None
    raise mf.ManifestError(f"no peaks for device_kind {device_kind!r} in "
                           f"benchmark/peaks.json, which has {sorted(table)}")


def _tiers(ctx: Context) -> List[str]:
    return list(ctx.served.entries)


def device_busy(ctx: Context) -> Tuple[float, float]:
    """(busy seconds averaged over the chips the tiers use, length of the
    traced window in seconds)."""
    lo, hi = ctx.trace["t_lo"], ctx.trace["t_hi"]
    devs = [d for t in _tiers(ctx) for d in ctx.tier_traces(t)]
    if not devs or hi <= lo:
        return 0.0, 0.0
    busy = sum(tracing.union_ns(d["ops"], lo, hi) for d in devs) / len(devs)
    return busy / 1e9, (hi - lo) / 1e9


def inflight(ctx: Context, host_t: float) -> int:
    return sum(1 for r in ctx.records
               if r["sent"] <= host_t and (r["end"] or 1e18) >= host_t)


def breakdown(ctx: Context) -> Dict[str, List[List[Any]]]:
    """The ten operations that took most device time, named
    ``<tier>:<kind of program>:<operation>``, and the ten longest idle
    gaps, named by the programs on either side and by how many requests
    the benchmark had in flight (no host spans exist in the program yet,
    so that is all a gap can be attributed to)."""
    lo, hi = ctx.trace["t_lo"], ctx.trace["t_hi"]
    ops: Dict[str, int] = {}
    gaps: List[Tuple[int, str]] = []
    for tier in _tiers(ctx):
        for dev in ctx.tier_traces(tier):
            mods = sorted(tracing.classify(dev), key=lambda m: m[1])
            starts = [m[1] for m in mods]

            def kind_at(t: int) -> str:
                i = bisect.bisect_right(starts, t) - 1
                if i >= 0 and mods[i][1] + mods[i][2] >= t:
                    return mods[i][0]
                return "none"
            for name, start, dur in dev["ops"]:
                if tracing.is_wrapper(name):
                    continue
                key = f"{tier}:{kind_at(start)}:{name}"
                ops[key] = ops.get(key, 0) + dur
            for start, length in tracing.gaps_ns(dev["ops"], lo, hi)[:10]:
                before = kind_at(start - 1) if start > lo else "start"
                after = kind_at(start + length + 1)
                n = inflight(ctx, ctx.host_time(start))
                why = "nothing_in_flight" if n == 0 else f"in_flight={n}"
                gaps.append((length, f"{tier}:{before}->{after}:{why}"))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[0])
    return {"device_ops": [[k, v / 1e9] for k, v in top],
            "idle_gaps": [[name, length / 1e9] for length, name in gaps[:10]]}
