"""Readers over the device trace (``tracing.py`` does the reduction; the
numerators of the shares come from ``costs.py``, which finds the counts
of the tier's own family, and ``peaks.json``)."""
from __future__ import annotations

import costs
import tracing
from cluster import say


def _modules(ctx, tier, kinds):
    """Whole program executions of the given kinds on the tier's first
    chip (the chips of a tensor-parallel tier run the same programs)."""
    devs = ctx.tier_traces(tier)
    if not devs:
        return []
    return [m for m in tracing.classify(devs[0]) if m[0] in kinds]


def decode_step_ms(ctx, tier):
    """Device time of one decode step: the decode ticks' device time over
    ticks x steps a tick."""
    ticks = _modules(ctx, tier, ("decode",))
    if not ticks:
        return None
    steps = ctx.served.entries[tier]["tier"].get("decode_steps_per_tick", 4)
    return sum(m[2] for m in ticks) / 1e6 / (len(ticks) * steps)


def _prefilled_tokens(ctx, tier):
    """Prompt tokens whose prefill fell inside the traced span: each
    request's prompt, weighted by how much of its sent-to-first-token
    interval (the server's own ``ttft_ms``) the span covers."""
    lo, hi = ctx.host_span
    total = 0.0
    for r in ctx.records:
        if r["device"] != tier or not r["server"] \
                or not r["server"].get("ttft_ms"):
            continue
        a, b = r["sent"], r["sent"] + r["server"]["ttft_ms"] / 1000.0
        cover = max(0.0, min(b, hi) - max(a, lo))
        total += r["prompt_tokens"] * cover / max(b - a, 1e-9)
    return total


def prefill_ms_per_ktok(ctx, tier):
    """Device time of everything that is not a decode tick (prefill
    chunks, cold prefills, block copies and writers of an admission) per
    thousand prompt tokens admitted in the traced span."""
    mods = _modules(ctx, tier, ("prefill", "other"))
    tokens = _prefilled_tokens(ctx, tier)
    if not mods or tokens < 1:
        return None
    return sum(m[2] for m in mods) / 1e6 / (tokens / 1000.0)


def _contexts(ctx, tier):
    """Positions each request in flight held at the middle of the traced
    span: its prompt plus the tokens generated by then (the client saw
    them up to 11 tokens late; the reply's length bounds the guess)."""
    mid = sum(ctx.host_span) / 2
    out = []
    for r in ctx.records:
        if r["device"] != tier or not r["stamps"] or r["end"] is None:
            continue
        if r["sent"] <= mid <= r["end"]:
            seen = sum(1 for s in r["stamps"] if s <= mid)
            out.append(r["prompt_tokens"] + min(len(r["stamps"]),
                                                seen + 11))
    return out


def decode_hbm_share(ctx, tier):
    """The least time the chip's memory needs for one decode step (its
    share of the weights once, plus the K/V of every sequence in flight)
    over the measured step time."""
    step = decode_step_ms(ctx, tier)
    if step is None or ctx.peaks is None:
        return None
    entry = ctx.served.entries[tier]
    contexts = _contexts(ctx, tier)
    need = costs.decode_step_bytes_per_chip(
        entry["model"], contexts, int(entry["tier"].get("tp", 1)),
        family=entry["family"])
    say("costs", f"tier {tier} ({entry['family']}): a decode step of "
                 f"{len(contexts)} sequences holding {sum(contexts)} "
                 f"positions reads at least {need!r} bytes a chip; the step "
                 f"took {step!r} ms")
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (step / 1000.0)


def idle_share(ctx, tier):
    """1 - busy union / traced window, averaged over the tier's chips."""
    lo, hi = ctx.trace["t_lo"], ctx.trace["t_hi"]
    devs = ctx.tier_traces(tier)
    if not devs or hi <= lo:
        return None
    busy = sum(tracing.union_ns(d["ops"], lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
