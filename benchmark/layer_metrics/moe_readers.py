"""Readers over the routed-expert counters of the program
(``dllm_moe_*`` on ``/metrics``, ``moe.expert_tokens`` on ``/stats``: the
assignments an expert that the decode tick and the chunk program return
beside their tokens).  A program without routed experts on its serving
path, or from before it counted them, has nothing to read here: every
reader returns None."""
from __future__ import annotations

import manifest as mf
from cluster import say
from layer_metrics import named_readers, trace_readers
from layer_metrics.span_readers import _delta


def experts_touched_per_step(ctx, tier):
    """Routed experts with at least one token, a decode step an expert
    layer: the delta of ``dllm_moe_experts_touched_total{stage=decode}``
    over decode steps (ticks x steps a tick) x expert layers.  The
    family's prediction at uniform routing is ``E (1 - (1 - k/E)^B)``."""
    touched = _delta(ctx, "dllm_moe_experts_touched_total", tier=tier,
                     stage="decode")
    ticks = _delta(ctx, "dllm_decode_ticks_total", tier=tier)
    if not touched or not ticks:
        return None
    entry = ctx.served.entries[tier]
    steps = ticks * entry["tier"].get("decode_steps_per_tick", 4)
    layers = (entry["model"]["num_hidden_layers"]
              - entry["model"]["first_k_dense_replace"])
    return touched / (steps * layers)


def load_max_over_mean(ctx, tier):
    """The busiest (layer, expert) of the window's decode steps over the
    mean one: ``/stats`` ``moe.expert_tokens.decode``, after minus
    before.  1 is an even load."""
    def tokens(stats):
        return stats["tiers"].get(tier, {}).get("moe", {}).get(
            "expert_tokens", {}).get("decode")
    a, b = tokens(ctx.stats_before), tokens(ctx.stats_after)
    if not a or not b:
        return None
    cells = [y - x for ra, rb in zip(a, b) for x, y in zip(ra, rb)]
    mean = sum(cells) / len(cells)
    return max(cells) / mean if mean > 0 else None


def decode_hbm_share_counted(ctx, tier):
    """The least time the chip's memory needs for one decode step over
    the measured step, as ``trace_readers.decode_hbm_share`` — but the
    routed experts in the byte count are the ones the program COUNTED
    (``experts_touched_per_step``), not the family's expectation at
    uniform routing, and the step is the whole ``jit_decode_tick``
    executions' (by name).  Prints the bytes it used, and the step by
    name beside the step by structure."""
    touched = experts_touched_per_step(ctx, tier)
    step = named_readers.decode_step_ms(ctx, tier)
    if touched is None or step is None or ctx.peaks is None:
        return None
    entry = ctx.served.entries[tier]
    contexts = trace_readers._contexts(ctx, tier)
    need = mf.load_family(entry["family"]).decode_step_bytes_per_chip(
        entry["model"], contexts, int(entry["tier"].get("tp", 1)),
        experts_touched=touched)
    say("costs", f"tier {tier} ({entry['family']}): a decode step of "
                 f"{len(contexts)} sequences holding {sum(contexts)} "
                 f"positions, {touched!r} experts a layer counted, reads at "
                 f"least {need!r} bytes a chip; the step took {step!r} ms "
                 f"by name, {trace_readers.decode_step_ms(ctx, tier)!r} ms "
                 f"by structure")
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (step / 1000.0)
