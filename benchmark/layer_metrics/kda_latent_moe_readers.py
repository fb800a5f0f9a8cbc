"""Readers for a tier of the delta-rule linear attention / latent
attention / routed-expert family (``families/kda_latent_moe_decoder.py``):
the held experts a decode step touches, a step's counted bytes (the
experts as the program COUNTED them, the latent rows of the contexts in
flight, the recurrent rows read and written) against the traced step and
by part, and the experts' grouped products' share of the step's device
time.  The family brings no kernel: its chunk recurrence and its step
update are XLA operations, read by ``step.chunk_ms`` and the step by name.
A tier of another family, a trace without decode ticks, or a program
without the routed-expert counters — one from before it served the family
— has nothing to read here: every reader returns None."""
from __future__ import annotations

import manifest as mf
from cluster import say
from layer_metrics import named_readers, trace_readers
from layer_metrics.span_readers import _delta

PRODUCT = "grouped_product"


def _family(ctx, tier):
    """(family module, the tier's entry), or None for a tier of another
    family: these readers count this family's parts."""
    entry = ctx.served.entries[tier]
    fam = mf.load_family(entry["family"])
    return (fam, entry) if hasattr(fam, "kda_mixer_params") else None


def held_experts_touched_per_step(ctx, tier):
    """Held experts with at least one token, a decode step an expert
    sublayer: the growth of ``dllm_moe_experts_touched_total{stage=
    decode}`` over decode steps (ticks x steps a tick) x the pattern's
    ``E`` sublayers.  The family's prediction at uniform routing is ``held
    (1 - (1 - k/E)^B)``."""
    found = _family(ctx, tier)
    touched = _delta(ctx, "dllm_moe_experts_touched_total", tier=tier,
                     stage="decode")
    ticks = _delta(ctx, "dllm_decode_ticks_total", tier=tier)
    if found is None or not touched or not ticks:
        return None
    fam, entry = found
    steps = ticks * entry["tier"].get("decode_steps_per_tick", 4)
    layers = fam.pattern(entry["preset"], entry["model"]).count("E")
    return touched / (steps * layers) if layers else None


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
    """The linear-attention rows (a float32 matrix a head and three conv
    tails a layer) read and written, over a decode step's counted bytes
    (%): what a slot costs a step whatever its length."""
    got = _parts(ctx, tier)
    if got is None:
        return None
    parts = got[0]
    return 100.0 * parts["state"] / sum(parts.values())


def latent_kv_share_of_step_bytes(ctx, tier):
    """The latent layers' rows of every position in flight, over a decode
    step's counted bytes (%): what the contexts cost a step beside the
    rows and the experts it touches."""
    got = _parts(ctx, tier)
    if got is None:
        return None
    parts, contexts, touched = got
    say("costs", f"tier {tier}: of a decode step's counted bytes "
                 f"{ {k: int(v) for k, v in parts.items()} } "
                 f"({len(contexts)} sequences holding {sum(contexts)} "
                 f"positions, {touched!r} held experts a layer counted) "
                 f"the latent rows are {parts['kv']!r} and the recurrent "
                 f"rows {parts['state']!r}")
    return 100.0 * parts["kv"] / sum(parts.values())


def decode_hbm_share_kda_latent_moe(ctx, tier):
    """The least time the chip's memory needs for one decode step over the
    measured step (%): the family's counted bytes at the experts the
    program COUNTED and the contexts the window's samples give, over the
    whole ``jit_decode_tick`` executions' step (by name).  Prints the
    bytes by part, and the step by name beside the step by structure."""
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


def grouped_product_share_of_step_ms(ctx, tier):
    """Device time of the ``grouped_product`` calls that ran inside whole
    ``jit_decode_tick`` executions over those executions' device time
    (%): the experts' measured share of a step, the rest being the
    linear-attention rows' update, latent attention, routers and head."""
    devs = ctx.tier_traces(tier)
    if _family(ctx, tier) is None or not devs:
        return None
    ticks = named_readers.executions(devs[0], "decode_tick",
                                     ctx.trace["t_lo"], ctx.trace["t_hi"])
    calls = sorted((s, d) for name, s, d in devs[0]["ops"]
                   if name.startswith(PRODUCT))
    if not ticks or not calls:
        return None
    inside, i = 0, 0
    for start, dur in ticks:
        while i < len(calls) and calls[i][0] < start:
            i += 1
        while i < len(calls) and calls[i][0] + calls[i][1] <= start + dur:
            inside += calls[i][1]
            i += 1
    total = sum(d for _, d in ticks)
    say("costs", f"tier {tier}: {len(ticks)} whole decode ticks of "
                 f"{total / len(ticks) / 1e6!r} ms in the mean, "
                 f"{inside / len(ticks) / 1e6!r} ms of each inside "
                 f"{PRODUCT}")
    return 100.0 * inside / total if inside else None
