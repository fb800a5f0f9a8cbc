"""Readers for a tier of the state-space / window-attention / shared-K/V
family (``families/ssm_window_shared_kv_decoder.py``): the parts of a
decode step's counted bytes (the one cached layer as its readers read it,
the rings, the recurrent rows); the chunk program's device time parted by
how deep each execution RAN (the loops the trace holds inside it: a
self-only chunk never enters the segments behind the cached layer); and
the share of prefill chunks the scheduler dispatched as self-only
(``dllm_prefill_self_only_chunks_total`` over
``dllm_prefill_chunks_total``).  A tier of another family, or a program
without that counter — one from before it served the family — has
nothing to read here: every reader returns None."""
from __future__ import annotations

import manifest as mf
from cluster import say
from layer_metrics import named_readers, trace_readers
from layer_metrics.span_readers import _delta


def _parts(ctx, tier):
    """(the family's counted bytes of a decode step by part, the contexts
    in flight mid-trace), or None."""
    entry = ctx.served.entries[tier]
    fam = mf.load_family(entry["family"])
    if not hasattr(fam, "ring_bytes_per_slot"):
        return None
    contexts = trace_readers._contexts(ctx, tier)
    if not contexts:
        return None
    return fam.decode_step_parts(entry["model"], contexts), contexts


def _share(ctx, tier, part):
    got = _parts(ctx, tier)
    if got is None:
        return None
    parts = got[0]
    return 100.0 * parts[part] / sum(parts.values())


def shared_kv_share_of_step_bytes(ctx, tier):
    """The one cached layer's K/V as its readers read them (each gathers
    the window anew), over a decode step's counted bytes (%)."""
    return _share(ctx, tier, "shared_kv")


def state_share_of_step_bytes(ctx, tier):
    """The recurrent state read and written, over a decode step's counted
    bytes (%): what a slot costs a step whatever its length."""
    return _share(ctx, tier, "state")


def decode_hbm_share_shared_kv(ctx, tier):
    """The least time the chip's memory needs for one decode step over the
    measured step (%): the family's counted bytes at the contexts the
    window's samples give, over the whole ``jit_decode_tick`` executions'
    step (by name).  Prints the bytes by part, and the step by name beside
    the step by structure."""
    got = _parts(ctx, tier)
    step = named_readers.decode_step_ms(ctx, tier)
    if got is None or step is None or ctx.peaks is None:
        return None
    parts, contexts = got
    need = sum(parts.values())
    say("costs", f"tier {tier} ({ctx.served.entries[tier]['family']}): a "
                 f"decode step of {len(contexts)} sequences holding "
                 f"{sum(contexts)} positions moves at least {need!r} bytes "
                 f"a chip { {k: int(v) for k, v in parts.items()} }; the "
                 f"step took {step!r} ms by name, "
                 f"{trace_readers.decode_step_ms(ctx, tier)!r} ms by "
                 f"structure")
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (step / 1000.0)


def ssm_chunk_scan_roofline(ctx, tier):
    """The chunk scan's kernel against the chip (%): the least time one
    call needs — the larger of its operations over the chip's peak and its
    bytes over the memory's rate, both from the family's counts at the
    tier's chunk length — over the mean device time of the executions the
    trace holds under the kernel's name.  Prints both bounds."""
    entry = ctx.served.entries[tier]
    fam = mf.load_family(entry["family"])
    devs = ctx.tier_traces(tier)
    if not hasattr(fam, "ssm_chunk_scan_bytes") or not devs \
            or ctx.peaks is None:
        return None
    runs = [e[2] for e in devs[0]["ops"] if e[0].startswith("ssm_chunk_scan")]
    if not runs:
        return None
    steps = int(entry["tier"].get("prefill_chunk_tokens") or 256)
    by_ops = (fam.ssm_chunk_scan_ops(entry["model"], steps)
              / ctx.peaks["bf16_flops_per_s"])
    by_bytes = (fam.ssm_chunk_scan_bytes(entry["model"], steps)
                / ctx.peaks["hbm_bytes_per_s"])
    mean_s = sum(runs) / len(runs) / 1e9
    say("costs", f"tier {tier}: ssm_chunk_scan over {steps} positions, "
                 f"{len(runs)} executions of {mean_s * 1e6!r} us in the "
                 f"mean; the chip needs {by_ops * 1e6!r} us for its "
                 f"operations, {by_bytes * 1e6!r} us for its bytes")
    return 100.0 * max(by_ops, by_bytes) / mean_s


def _chunks_by_depth(ctx, tier):
    """Device ms of every whole ``jit_chunk_prefill`` execution on the
    tier's first chip, parted by the ``while`` loops the trace holds
    INSIDE it: (self-only, full-depth), the counts of loops from the
    family (``chunk_loops``).  What ran, not what was asked: a program
    that took the deep branch every time, or had none to skip, files
    every execution as full-depth.  None for another family, a trace
    without chunks, or an execution with another count of loops (then the
    program is not the one the family describes)."""
    entry = ctx.served.entries[tier]
    fam = mf.load_family(entry["family"])
    devs = ctx.tier_traces(tier)
    if not hasattr(fam, "chunk_loops") or not devs:
        return None
    cached = ctx.__dict__.setdefault("_chunks_by_depth", {})
    if tier in cached:
        return cached[tier]
    chunks = named_readers.executions(devs[0], "chunk_prefill",
                                      ctx.trace["t_lo"], ctx.trace["t_hi"])
    if not chunks:
        return None
    shallow, deep = fam.chunk_loops(entry["model"])
    whiles = [e for e in devs[0]["ops"] if e[0].startswith("while")]
    by_loops = {shallow: [], deep: []}
    for start, dur in chunks:
        loops = sum(1 for _, s, d in whiles
                    if s >= start and s + d <= start + dur)
        if loops not in by_loops:
            say("costs", f"tier {tier}: a chunk program of {loops} loops "
                         f"(the family's run {shallow} or {deep}): not "
                         f"parted by depth")
            cached[tier] = None
            return None
        by_loops[loops].append(dur / 1e6)
    alone, full = by_loops[shallow], by_loops[deep]
    mean = [sum(x) / len(x) if x else None for x in (alone, full)]
    say("costs", f"tier {tier}: {len(chunks)} whole chunk programs in the "
                 f"trace, {len(alone)} self-only ({shallow} loop(s)) of "
                 f"{mean[0]!r} ms in the mean, {len(full)} full-depth "
                 f"({deep}) of {mean[1]!r} ms")
    cached[tier] = (alone, full)
    return cached[tier]


def _chunk_ms(ctx, tier, which):
    got = _chunks_by_depth(ctx, tier)
    if got is None or not got[which]:
        return None
    return sum(got[which]) / len(got[which])


def chunk_ms_self_only(ctx, tier):
    """Device time of one chunk program that ran no layer behind the
    cached layer's K/V write (ms): 15 of a 16-chunk prompt's."""
    return _chunk_ms(ctx, tier, 0)


def chunk_ms_full_depth(ctx, tier):
    """Device time of one chunk program that ran every layer (ms): the
    one that holds its prompt's last token."""
    return _chunk_ms(ctx, tier, 1)


def self_only_chunk_share(ctx, tier):
    """Of the prefill chunks the scheduler dispatched in the run, the
    share it counted as not holding their prompt's last token (%): 15 of
    16 when every prompt is 16 chunks.  A count of dispatches, taken on
    the host from the same test the program makes; what the device ran is
    ``chunk_ms_self_only`` / ``chunk_ms_full_depth``."""
    chunks = _delta(ctx, "dllm_prefill_chunks_total", tier=tier)
    if chunks <= 0 or "dllm_prefill_self_only_chunks_total" \
            not in ctx.metrics_after:
        return None
    return 100.0 * _delta(ctx, "dllm_prefill_self_only_chunks_total",
                          tier=tier) / chunks
