"""Readers for a tier of the rotary latent attention / routed-expert
family (``families/rotary_latent_moe_decoder.py``): what share of the
chunk programs' device time runs under the scopes of the latent sublayer
and of the experts (by the scopes the PROGRAM names, ``scope_readers``),
the share of a chunk's token-to-expert assignments that went to experts
this program holds, and a decode step's counted bytes against the traced
step.  The family brings no kernel.  A tier of another family, a program
without ``GET /debug/programs`` or without the routed-expert counters — one
from before it served the family — or a trace without whole chunk
programs or decode ticks has nothing to read here: every reader returns
None."""
from __future__ import annotations

import manifest as mf
from cluster import say
from layer_metrics import named_readers, scope_readers, trace_readers
from layer_metrics.span_readers import _delta


def _family(ctx, tier):
    """(family module, the tier's entry), or None for a tier of another
    family: these readers count this family's parts."""
    entry = ctx.served.entries[tier]
    if entry["family"] != "rotary_latent_moe_decoder":
        return None
    return mf.load_family(entry["family"]), entry


def scope_share_of_chunk_ms(ctx, tier, scopes):
    """Device time of the operations whose scope, by the map of the
    execution's own program, is one of ``scopes``, over the whole
    ``jit_chunk_prefill`` executions' device time (%).  A fusion across
    scopes that do not nest counts for none."""
    if _family(ctx, tier) is None:
        return None
    got = scope_readers.reduce_program(ctx, tier, "chunk_prefill")
    if got is None or not got["modules_ns"]:
        return None
    inside = sum(got["by_scope_ns"].get(s, 0) for s in scopes)
    say("costs", f"tier {tier}: of {got['executions']} whole chunk "
                 f"programs' {got['modules_ns'] / 1e6!r} ms on the device, "
                 f"{inside / 1e6!r} ms under {list(scopes)}")
    return 100.0 * inside / got["modules_ns"] if inside else None


def held_assignment_share_prefill(ctx, tier):
    """Of the chunk programs' token-to-expert assignments (padding rows
    too: the device computed them), the share that went to experts this
    program holds (%): held / (held + absent).  25 at uniform routing over
    a router of which a quarter is held."""
    if _family(ctx, tier) is None:
        return None
    held = _delta(ctx, "dllm_moe_assignments_total", tier=tier,
                  stage="prefill")
    absent = _delta(ctx, "dllm_moe_absent_assignments_total", tier=tier,
                    stage="prefill")
    if not held or held + absent <= 0:
        return None
    return 100.0 * held / (held + absent)


def _held_experts_touched_per_step(ctx, tier, fam, entry):
    """Held experts with at least one token, a decode step an expert
    sublayer, as the program counted them."""
    touched = _delta(ctx, "dllm_moe_experts_touched_total", tier=tier,
                     stage="decode")
    ticks = _delta(ctx, "dllm_decode_ticks_total", tier=tier)
    layers = fam.pattern(entry["preset"], entry["model"]).count("E")
    if not touched or not ticks or not layers:
        return None
    steps = ticks * entry["tier"].get("decode_steps_per_tick", 4)
    return touched / (steps * layers)


def decode_hbm_share_rotary_latent_moe(ctx, tier):
    """The least time the chip's memory needs for one decode step over the
    measured step (%): the family's counted bytes at the experts the
    program COUNTED and the contexts the window's samples give, over the
    whole ``jit_decode_tick`` executions' step (by name).  Prints the
    bytes by part."""
    found = _family(ctx, tier)
    step = named_readers.decode_step_ms(ctx, tier)
    if found is None or step is None or ctx.peaks is None:
        return None
    fam, entry = found
    touched = _held_experts_touched_per_step(ctx, tier, fam, entry)
    contexts = trace_readers._contexts(ctx, tier)
    if touched is None or not contexts:
        return None
    parts = fam.decode_step_parts(entry["model"], contexts, touched)
    need = sum(parts.values())
    say("costs", f"tier {tier} ({entry['family']}): a decode step of "
                 f"{len(contexts)} sequences holding {sum(contexts)} "
                 f"positions, {touched!r} held experts a layer counted, "
                 f"moves at least {need!r} bytes a chip "
                 f"{ {k: int(v) for k, v in parts.items()} }; the step "
                 f"took {step!r} ms by name")
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (step / 1000.0)
