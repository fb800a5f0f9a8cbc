"""Readers for a tier of the state-space / attention / routed-expert
hybrid family (``families/hybrid_ssm_moe_decoder.py``): a program that
HOLDS a share of each expert layer's experts counts the assignments to
the experts it holds (``dllm_moe_assignments_total``,
``dllm_moe_experts_touched_total``) and, beside them, those that went to
absent ones (``dllm_moe_absent_assignments_total``).  A program without
those counters — one from before it served the family — has nothing to
read here: every reader returns None."""
from __future__ import annotations

import manifest as mf
from cluster import say
from layer_metrics import named_readers, trace_readers
from layer_metrics.span_readers import _delta


def _family(ctx, tier):
    """(family module, the tier's entry), or None for a tier of another
    family: these readers count this family's parts."""
    entry = ctx.served.entries[tier]
    fam = mf.load_family(entry["family"])
    return (fam, entry) if hasattr(fam, "decode_step_parts") else None


def _decode_steps(ctx, tier, entry):
    ticks = _delta(ctx, "dllm_decode_ticks_total", tier=tier)
    return ticks * entry["tier"].get("decode_steps_per_tick", 4)


def held_experts_touched_per_step(ctx, tier):
    """Held experts with at least one token, a decode step an expert
    layer: the growth of ``dllm_moe_experts_touched_total{stage=decode}``
    over decode steps x the ``E`` layers of the pattern.  The family's
    prediction at uniform routing is ``held (1 - (1 - k/E)^B)``."""
    found = _family(ctx, tier)
    touched = _delta(ctx, "dllm_moe_experts_touched_total", tier=tier,
                     stage="decode")
    if found is None or not touched:
        return None
    _, entry = found
    steps = _decode_steps(ctx, tier, entry)
    layers = entry["model"]["hybrid_override_pattern"].count("E")
    return touched / (steps * layers) if steps and layers else None


def held_assignment_share(ctx, tier):
    """Of the decode steps' token-to-expert assignments, the share that
    went to experts this program holds (%): held / (held + absent).  50
    at uniform routing over a router of which half is held."""
    if _family(ctx, tier) is None:
        return None
    held = _delta(ctx, "dllm_moe_assignments_total", tier=tier,
                  stage="decode")
    absent = _delta(ctx, "dllm_moe_absent_assignments_total", tier=tier,
                    stage="decode")
    if not held or held + absent <= 0:
        return None
    return 100.0 * held / (held + absent)


def _parts(ctx, tier):
    """The family's counted bytes of a decode step at the experts the
    program counted and the contexts in flight mid-trace, by part."""
    found = _family(ctx, tier)
    touched = held_experts_touched_per_step(ctx, tier)
    if found is None or touched is None:
        return None
    fam, entry = found
    contexts = trace_readers._contexts(ctx, tier)
    if not contexts:
        return None
    return fam.decode_step_parts(entry["model"], contexts, touched), \
        contexts, touched


def state_share_of_step_bytes(ctx, tier):
    """The recurrent state read and written, over a decode step's counted
    bytes (%): what a slot costs a step whatever its length."""
    got = _parts(ctx, tier)
    if got is None:
        return None
    parts = got[0]
    return 100.0 * parts["state"] / sum(parts.values())


def decode_hbm_share_hybrid(ctx, tier):
    """The least time the chip's memory needs for one decode step over the
    measured step (%): the family's counted bytes at the experts the
    program COUNTED, over the whole ``jit_decode_tick`` executions' step
    (by name).  Prints the bytes by part, and the step by name beside the
    step by structure."""
    got = _parts(ctx, tier)
    step = named_readers.decode_step_ms(ctx, tier)
    if got is None or step is None or ctx.peaks is None:
        return None
    parts, contexts, touched = got
    need = sum(parts.values())
    say("costs", f"tier {tier} ({ctx.served.entries[tier]['family']}): a "
                 f"decode step of {len(contexts)} sequences holding "
                 f"{sum(contexts)} positions, {touched!r} held experts a "
                 f"layer counted, moves at least {need!r} bytes a chip "
                 f"{ {k: int(v) for k, v in parts.items()} }; the step "
                 f"took {step!r} ms by name, "
                 f"{trace_readers.decode_step_ms(ctx, tier)!r} ms by "
                 f"structure")
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (step / 1000.0)
