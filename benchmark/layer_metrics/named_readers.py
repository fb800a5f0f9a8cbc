"""Readers that find a step program on the trace's "XLA Modules" line
by its NAME (``jit_decode_tick``, ``jit_chunk_prefill``: the program
names its jitted step functions since PR 26), whatever loops it holds.
``trace_readers.py`` tells the programs apart by structure; the two
agree as long as a decode tick nests two ``while`` loops
(``tests/test_span_readers.py`` holds them together on a recorded
second).  A program that names all its steps ``jit_run`` (the commits
before PR 26) has nothing to read here: the readers return None."""
from __future__ import annotations

MIN_NS = 100_000        # as tracing.classify: shorter executions are not steps
EDGE_NS = 10_000        # an execution this close to the capture's edge is cut


def executions(dev, program, t_lo=None, t_hi=None):
    """[start_ns, dur_ns] of every execution of ``jit_<program>`` on one
    device, in time order.  A module event is named
    ``jit_<program>(<fingerprint>)``.

    A capture that starts or stops while a program runs records the part
    it saw: an event that begins with the capture's first device event
    (``t_lo``) or ends with its last (``t_hi``).  With the bounds given,
    those cut executions are left out (the structural classification
    drops them too: a cut decode tick has lost its outer loop)."""
    want = "jit_" + program
    return sorted(
        [m[1], m[2]] for m in dev["modules"]
        if m[0].split("(", 1)[0] == want and m[2] >= MIN_NS
        and (t_lo is None or m[1] > t_lo + EDGE_NS)
        and (t_hi is None or m[1] + m[2] < t_hi - EDGE_NS))


def _whole_executions(ctx, tier, program):
    devs = ctx.tier_traces(tier)
    if not devs:
        return []
    return executions(devs[0], program, ctx.trace["t_lo"], ctx.trace["t_hi"])


def decode_step_ms(ctx, tier):
    """Device time of one decode step: the whole ``jit_decode_tick``
    executions' device time over executions x steps a tick."""
    ticks = _whole_executions(ctx, tier, "decode_tick")
    if not ticks:
        return None
    steps = ctx.served.entries[tier]["tier"].get("decode_steps_per_tick", 4)
    return sum(d for _, d in ticks) / 1e6 / (len(ticks) * steps)


def chunk_prefill_ms(ctx, tier):
    """Device time of one whole ``jit_chunk_prefill`` execution, the
    count taken from the trace itself."""
    chunks = _whole_executions(ctx, tier, "chunk_prefill")
    if not chunks:
        return None
    return sum(d for _, d in chunks) / 1e6 / len(chunks)
