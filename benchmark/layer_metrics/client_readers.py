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
