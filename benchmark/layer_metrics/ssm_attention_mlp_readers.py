"""Readers for a tier of the state-space / attention / dense-MLP family
(``families/ssm_attention_mlp_decoder.py``), over the device trace: what
share of the chunk programs' device time the chunk scan's kernel takes,
the chunk programs' matrix products against the chip's peak, and a decode
step's counted bytes against the traced step.  A tier of another family,
a trace without chunk programs, or a program without the counter of
chunks by window rung — one from before it served the family — has
nothing to read here: every reader returns None."""
from __future__ import annotations

import manifest as mf
from cluster import say
from layer_metrics import named_readers, trace_readers
from layer_metrics.span_readers import _delta, _series

SCAN = "ssm_chunk_scan"


def _family(ctx, tier):
    """(family module, the tier's entry), or None for a tier of another
    family."""
    entry = ctx.served.entries[tier]
    fam = mf.load_family(entry["family"])
    return (fam, entry) if hasattr(fam, "chunk_flops_per_chip") else None


def _chunks(ctx, tier):
    """[start_ns, dur_ns] of the whole ``jit_chunk_prefill`` executions on
    the tier's first chip that hold as many ``while`` loops as the family
    says its chunk program runs (another count: not the program the
    family describes, left out), with the chip's operations."""
    found = _family(ctx, tier)
    devs = ctx.tier_traces(tier)
    if found is None or not devs:
        return None
    fam, entry = found
    loops = fam.chunk_loops(entry["model"])
    whiles = [e for e in devs[0]["ops"] if e[0].startswith("while")]
    chunks = [
        (start, dur) for start, dur in named_readers.executions(
            devs[0], "chunk_prefill", ctx.trace["t_lo"], ctx.trace["t_hi"])
        if sum(1 for _, s, d in whiles
               if s >= start and s + d <= start + dur) == loops]
    return (chunks, devs[0]["ops"]) if chunks else None


def chunk_scan_share_of_chunk_ms(ctx, tier):
    """Device time of the ``ssm_chunk_scan`` calls that ran inside whole
    chunk programs over those programs' device time (%): what a faster
    scan kernel could take off a chunk, and so off the time to a first
    token."""
    got = _chunks(ctx, tier)
    if got is None:
        return None
    chunks, ops = got
    scans = sorted((s, d) for name, s, d in ops if name.startswith(SCAN))
    inside, i = 0, 0
    for start, dur in chunks:
        while i < len(scans) and scans[i][0] < start:
            i += 1
        while i < len(scans) and scans[i][0] + scans[i][1] <= start + dur:
            inside += scans[i][1]
            i += 1
    total = sum(d for _, d in chunks)
    say("costs", f"tier {tier}: {len(chunks)} whole chunk programs of "
                 f"{total / len(chunks) / 1e6!r} ms in the mean, "
                 f"{inside / len(chunks) / 1e6!r} ms of each inside "
                 f"{SCAN}")
    return 100.0 * inside / total if inside else None


def _chunks_by_window(ctx, tier):
    """{window rung: chunks the lane dispatched at it during the run},
    from the program's counter, both sides of the run."""
    name = "dllm_prefill_chunks_by_window_total"
    windows = {lab["window"] for lab, _ in _series(ctx.metrics_after, name)
               if lab.get("tier") == tier}
    grown = {int(w): _delta(ctx, name, tier=tier, window=w) for w in windows}
    return {w: n for w, n in grown.items() if n > 0}


def chunk_mfu(ctx, tier):
    """The chunk programs' matrix products over the chip's bf16 peak (%):
    the family's ``chunk_flops_per_chip`` at the window rungs the lane's
    chunks RAN at (the program's count of chunks by rung over the run:
    every request of the mix runs the same chunks, so the run's mix of
    rungs is the traced window's) over the mean device time of the whole
    chunk programs in the trace."""
    got = _chunks(ctx, tier)
    found = _family(ctx, tier)
    if got is None or found is None or ctx.peaks is None:
        return None
    by_window = _chunks_by_window(ctx, tier)
    if not by_window:
        return None
    fam, entry = found
    chunks, _ = got
    steps = int(entry["tier"].get("prefill_chunk_tokens") or 256)
    n = sum(by_window.values())
    flops = sum(count * fam.chunk_flops_per_chip(entry["model"], steps, w)
                for w, count in by_window.items()) / n
    mean_s = sum(d for _, d in chunks) / len(chunks) / 1e9
    say("costs", f"tier {tier}: a chunk program of {steps} positions is "
                 f"{flops!r} operations of matrix products in the mean "
                 f"over the rungs the run's {int(n)} chunks ran at "
                 f"{ {w: int(c) for w, c in sorted(by_window.items())} }; "
                 f"the {len(chunks)} traced took {mean_s * 1e3!r} ms in "
                 f"the mean")
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / mean_s


def decode_hbm_share_ssm_attention(ctx, tier):
    """The least time the chip's memory needs for one decode step over the
    measured step (%): the family's counted bytes at the contexts the
    window's samples give, over the whole ``jit_decode_tick`` executions'
    step (by name).  Prints the bytes by part."""
    found = _family(ctx, tier)
    step = named_readers.decode_step_ms(ctx, tier)
    if found is None or step is None or ctx.peaks is None:
        return None
    fam, entry = found
    contexts = trace_readers._contexts(ctx, tier)
    if not contexts:
        return None
    parts = fam.decode_step_parts(entry["model"], contexts)
    need = sum(parts.values())
    say("costs", f"tier {tier} ({entry['family']}): a decode step of "
                 f"{len(contexts)} sequences holding {sum(contexts)} "
                 f"positions moves at least {need!r} bytes a chip "
                 f"{ {k: int(v) for k, v in parts.items()} }; the step "
                 f"took {step!r} ms by name")
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (step / 1000.0)
