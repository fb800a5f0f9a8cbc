"""Device time by ``jax.named_scope``: the trace's operations of the
step programs, each filed under the scope the PROGRAM says it belongs
to.  ``GET /debug/programs`` (``serving/app.py``; built on its first
request, ``obs/program_scopes.py``) maps every operation of every decode
tick and chunk program the engine compiled to its innermost scope; a
traced ``jit_decode_tick(<n>)`` / ``jit_chunk_prefill(<n>)`` is joined to
the map that names the most of its operations.  Only the programs a trace
holds are asked about (``programs``): the first request about one
compiles it.

One reduction a (tier, program), printed once as a ``[bench:scopes]``
line: every scope's milliseconds (``null``: no scope; ``mixed``: a fusion
across scopes that do not nest), and the program's device time by window
rung.  A program without the route (the commits before it: 404), a trace
without a whole execution, or a tier that runs no such program has
nothing to read: every reader returns None."""
from __future__ import annotations

import json
import time

import tracing
from cluster import say
from layer_metrics import named_readers

ROUTE = "/debug/programs"
NULL, MIXED = "null", "mixed"


def _ask(ctx, query):
    """One request of the route: its document, or None where the route
    is not there; the seconds every request took are kept on the
    context, with what the program says building took, by part."""
    spent = ctx.__dict__.setdefault(
        "_scope_asked", {"requests": 0, "seconds": 0.0, "built_s": {}})
    t0 = time.perf_counter()
    resp = ctx.served.client.get(ROUTE + query)
    doc = resp.get_json() if resp.status_code == 200 else None
    spent["requests"] += 1
    spent["seconds"] += time.perf_counter() - t0
    return doc


def _lane_windows_run(ctx, tier):
    """The window rungs whose count of lane chunks grew over the run
    (``/stats`` ``prefill.chunks_by_window``, before against after): the
    chunk programs to ask about first.  A prefix-hit admission's suffix
    chunk is not counted there, so the others are asked about after them
    where a traced program is still without a name."""
    def counts(stats):
        block = ((stats or {}).get("tiers", {}).get(tier) or {})
        return (block.get("prefill") or {}).get("chunks_by_window") or {}
    before = counts(getattr(ctx, "stats_before", None))
    after = counts(getattr(ctx, "stats_after", None))
    return {int(w) for w, n in after.items() if n > before.get(w, 0)}


def programs(ctx, tier, stage, traced):
    """The tier's entries of ``GET /debug/programs`` for the programs of
    ``stage`` that ``traced`` ({module name: {operation: ns}}) holds, or
    None where the route is not there.  The first request about a program
    compiles it again, so not every warmed rung is asked about: the chunk
    programs of the rungs the run's lane chunks used at once (the engine
    builds them side by side), then the others; the tick's rungs one
    request each from the widest down; either until every traced program
    has an entry that names all of its operations.  Gives (entries,
    windows not asked about)."""
    listed = _ask(ctx, f"?ops=0&stage={stage}")
    listed = (listed or {}).get("tiers", {}).get(tier)
    if not listed:
        return None
    windows = sorted({e["window_tokens"] for e in listed}, reverse=True)
    if stage == "chunk_prefill":
        ran = _lane_windows_run(ctx, tier)
        batches = [b for b in ([w for w in windows if w in ran],
                               [w for w in windows if w not in ran]) if b]
    else:
        batches = [[w] for w in windows]
    entries, left = [], dict(traced)
    while batches and left:
        batch = batches.pop(0)
        doc = _ask(ctx, f"?stage={stage}&window_tokens="
                        + ",".join(map(str, batch)))
        got = (doc or {}).get("tiers", {}).get(tier) or []
        spent = ctx._scope_asked["built_s"]
        for e in got:
            for part, seconds in (e.get("built_s") or {}).items():
                spent[part] = spent.get(part, 0.0) + seconds
        entries += got
        left = {name: inside for name, inside in left.items()
                if not any(_names_all(e, inside) for e in got)}
    return entries, [w for batch in batches for w in batch]


def _inside(ops_in_time, spans):
    """{operation: ns} of the non-wrapper operations that start inside
    one of ``spans`` [[start, dur], ...], both in time order; names
    without a custom call's ``@target``."""
    inside, i = {}, 0
    for name, start, dur in ops_in_time:
        while i < len(spans) and sum(spans[i]) <= start:
            i += 1
        if i == len(spans):
            break
        if spans[i][0] <= start and not tracing.is_wrapper(name):
            name = name.split("@")[0]
            inside[name] = inside.get(name, 0) + dur
    return inside


def _breaks(entry, inside):
    """How often the trace's order of first appearance runs against the
    entry's own order of operations (``ops`` keeps the compiled text's):
    a few times at the loops' edges for the program itself, all over for
    one that holds the same names numbered otherwise."""
    rank = {op: i for i, op in enumerate(entry["ops"])}
    seen = [rank[op] for op in inside if op in rank]
    return sum(a > b for a, b in zip(seen, seen[1:]))


def _names_all(entry, inside):
    """Whether the entry may be the traced program itself: it names all
    of its operations, in an order the trace breaks for under a tenth of
    them."""
    return (inside.keys() <= entry["ops"].keys()
            and _breaks(entry, inside) <= len(inside) // 10)


def _match(entries, inside):
    """The map entries a traced program may be: those that name the most
    of its operations and, among them, those whose order the trace breaks
    least.  No runtime gives what stands in the parentheses of the
    trace's module name, so rungs whose programs name and order their
    operations alike come back together."""
    named = [len(inside.keys() & e["ops"].keys()) for e in entries]
    most = [e for e, n in zip(entries, named) if n and n == max(named)]
    least = min((_breaks(e, inside) for e in most), default=0)
    return [e for e in most if _breaks(e, inside) == least]


def reduce_program(ctx, tier, program):
    """The whole ``jit_<program>`` executions of the tier's first chip by
    scope: ``{"executions", "modules_ns", "ops_ns", "by_scope_ns":
    {scope | "null" | "mixed": ns}, "rungs": [{"traced_as",
    "window_tokens", "alike", "chunk_tokens", "executions", "mean_ms",
    "by_scope_ms", "by_op_ns"}],
    "not_told_apart": [{"windows", "maps_agree"}], "not_asked": [window],
    "top_unscoped": [[op, ms]]}``, computed once a context, or None."""
    cache = ctx.__dict__.setdefault("_scope_reduced", {})
    if (tier, program) not in cache:
        cache[tier, program] = _reduce_program(ctx, tier, program)
    return cache[tier, program]


def _reduce_program(ctx, tier, program):
    stage = {"decode_tick": "decode", "chunk_prefill": "chunk_prefill"}[
        program]
    devs = ctx.tier_traces(tier)
    if not devs:
        return None
    dev = devs[0]
    by_module = {}
    for m in dev["modules"]:
        if m[0].split("(", 1)[0] == "jit_" + program:
            by_module.setdefault(m[0], []).append(m)
    in_time = sorted(dev["ops"], key=lambda e: e[1])
    traced = {}
    for name, modules in sorted(by_module.items()):
        spans = named_readers.executions(
            {"modules": modules}, program, ctx.trace["t_lo"],
            ctx.trace["t_hi"])
        if spans:
            traced[name] = (spans, _inside(in_time, spans))
    got = traced and programs(ctx, tier, stage,
                              {k: v[1] for k, v in traced.items()})
    if not got:
        return None
    entries, not_asked = got
    out = {"executions": 0, "modules_ns": 0, "ops_ns": 0,
           "by_scope_ns": {}, "rungs": [], "not_told_apart": [],
           "not_asked": not_asked, "top_unscoped": {}}
    for name, (spans, inside) in traced.items():
        found = _match(entries, inside)
        if not found:
            continue
        # Rungs whose programs name their operations alike cannot be told
        # apart in a trace, and where their maps agree on every operation
        # seen need not be for the scopes: the row is filed under the
        # widest, and the line names them all.
        found.sort(key=lambda e: e["window_tokens"])
        entry = found[-1]
        if len(found) > 1:
            out["not_told_apart"].append({
                "windows": [e["window_tokens"] for e in found],
                "maps_agree": all(e["ops"].get(op) == entry["ops"].get(op)
                                  for e in found for op in inside)})
        by_scope = {}
        for op, ns in inside.items():
            at = entry["ops"].get(op) or {"scope": None, "mixed": False}
            key = MIXED if at["mixed"] else at["scope"] or NULL
            by_scope[key] = by_scope.get(key, 0) + ns
            if key in (NULL, MIXED):
                label = f"{op}:{at['scope']}" if at["mixed"] else op
                out["top_unscoped"][label] = \
                    out["top_unscoped"].get(label, 0) + ns
        n, module_ns = len(spans), sum(d for _, d in spans)
        out["executions"] += n
        out["modules_ns"] += module_ns
        out["ops_ns"] += sum(inside.values())
        for key, ns in by_scope.items():
            out["by_scope_ns"][key] = out["by_scope_ns"].get(key, 0) + ns
        out["rungs"].append({
            "traced_as": name,
            "window_tokens": entry["window_tokens"],
            "alike": sorted(e["window_tokens"] for e in found[:-1]),
            "chunk_tokens": entry["chunk_tokens"],
            "attention_form": entry.get("attention_form"),
            "executions": n, "mean_ms": module_ns / n / 1e6,
            "by_scope_ms": {k: v / n / 1e6 for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])},
            # Not printed: every operation's ns in all, with its entry.
            "by_op_ns": {op: [ns, entry["ops"].get(op)]
                         for op, ns in inside.items()}})
    if not out["executions"]:
        return None
    out["rungs"].sort(key=lambda r: (r["window_tokens"],
                                     r["chunk_tokens"] or 0))
    out["top_unscoped"] = [[op, ns / 1e6] for op, ns in sorted(
        out["top_unscoped"].items(), key=lambda kv: -kv[1])[:8]]
    per = _per(ctx, tier, program, out["executions"])
    say("scopes", f"tier {tier}: {out['executions']} whole jit_{program} "
                  f"executions, device ms a "
                  f"{'step' if program == 'decode_tick' else 'chunk'}: "
                  f"the programs {out['modules_ns'] / per!r}, their "
                  f"operations {out['ops_ns'] / per!r}, by scope "
                  + json.dumps({k: v / per for k, v in sorted(
                      out["by_scope_ns"].items(), key=lambda kv: -kv[1])})
                  + "; by window rung (ms an execution) "
                  + json.dumps([{k: v for k, v in r.items()
                                 if k != "by_op_ns"} for r in out["rungs"]])
                  + "; rungs the trace could not tell apart "
                  + json.dumps(out["not_told_apart"])
                  + "; narrower rungs not asked about "
                  + json.dumps(out["not_asked"])
                  + f"; {ROUTE} so far " + json.dumps(ctx._scope_asked)
                  + "; longest under null or mixed (ms in all) "
                  + json.dumps(out["top_unscoped"]))
    return out


def _per(ctx, tier, program, executions):
    """Nanoseconds -> milliseconds a step (the tick: executions x steps a
    tick) or a chunk (executions)."""
    steps = (ctx.served.entries[tier]["tier"].get("decode_steps_per_tick", 4)
             if program == "decode_tick" else 1)
    return 1e6 * executions * steps


def scope_ms(ctx, tier, program, scopes):
    """Device time of the operations whose scope, by the map of the
    execution's own program, is one of ``scopes``, inside whole
    ``jit_<program>`` executions: ms a decode step (``decode_tick``) or a
    chunk (``chunk_prefill``).  A fusion across scopes that do not nest
    is ``mixed`` and counts for none."""
    got = reduce_program(ctx, tier, program)
    if got is None:
        return None
    ns = sum(got["by_scope_ns"].get(s, 0) for s in scopes)
    return ns / _per(ctx, tier, program, got["executions"])


def unscoped_share(ctx, tier):
    """Of the whole decode ticks' operation time, the share under no
    scope or under a fusion across scopes (%)."""
    got = reduce_program(ctx, tier, "decode_tick")
    if got is None or not got["ops_ns"]:
        return None
    return 100.0 * (got["by_scope_ns"].get(NULL, 0)
                    + got["by_scope_ns"].get(MIXED, 0)) / got["ops_ns"]
