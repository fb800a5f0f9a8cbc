"""Readers over the client's own request records."""
from __future__ import annotations

import e2e


def _due(ctx):
    return e2e.in_window(ctx.records, ctx.t0, ctx.seconds)


def ttft_percentile(ctx, q):
    """A percentile of client TTFT that is too unsteady to judge a PR by
    (a p90 over a dozen requests is the largest but one)."""
    vals = [x for x in (e2e.ttft_ms(r) for r in _due(ctx)) if x is not None]
    return e2e.percentile(vals, q)


def tpot_percentile(ctx, q):
    """A percentile of the client's time per token while a reply
    streams.  Not judged end to end: one 100 ms stall of the machine
    adds 1 ms a token to the 8 replies in flight, 3 % of a window's
    requests, so four stalls carry the p90 across a cliff (PERF.md
    section 6, PR 28)."""
    vals = [x for x in (e2e.tpot_ms(r) for r in _due(ctx)) if x is not None]
    return e2e.percentile(vals, q)


def token_rate(ctx):
    """Output tokens whose arrival stamp lies in the window, a second:
    a rate over all the work and all the time of the window, so every
    stall of the machine is in it (why it is not judged end to end)."""
    return e2e.tokens_in_window(ctx.records, ctx.t0, ctx.seconds) / ctx.seconds
