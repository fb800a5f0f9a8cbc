"""Run one benchmark cell (``benchmark/run.py``, same arguments) and,
before the cluster drains, print what the result line does not carry:
each tier's GET /stats ``tick`` and ``prefill`` blocks (the resident
share of PR 36, the ``ahead_share`` and ``ahead_dead_slot_steps_total``
of PR 52: ticks dispatched ahead of the fetch before them, of the ticks
launched over the engine's life; the riding chunks of PR 32), what each
stage's program traced its routed experts' FFN with
(``moe.grouped_product``: PR 53) and, from ``/metrics``
before the traffic and after it, who held the interpreter (PR 41): the
scheduler's CPU / off-CPU / run-queue milliseconds a tick, every
phase's self wall beside its self CPU, and the edge lanes' block, by the
benchmark's own readers
(``benchmark/layer_metrics/host_readers.py``), so an untraced run shows
them too; and, from ``/debug/trace`` (the rings' last two minutes), the
scheduler phase that stamped each awake slice's first token.  With
``--trace 1``, the chunk programs' device time by COMPILED PROGRAM (one a
window rung, PR 42): the whole ``jit_chunk_prefill`` executions of the
device trace grouped by the fingerprint in their name; and the decode
tick's ATTENTION scope by rung (PR 45): the device time a step of the
operations whose HLO metadata names the ``attention`` scope — the window
traffic: a gather and two products a side in the ``merged`` form, one
kernel in the ``streamed`` one — read off each warmed rung's own
compiled program, by operation.

    python3 scripts/bench_stats.py --workload smollm2-1.7b.decode-closed \
        --seed 7 --seconds 50 --trace 0

From the root of a checkout, on the chip.  Nothing of ``benchmark/`` is
edited: ``cluster.Served.drain`` and ``drive.Run.run`` are wrapped in
this process only.
"""

import json
import os
import re
import runpy
import sys
import types

ROOT = os.getcwd()
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import cluster                                   # noqa: E402
import drive                                     # noqa: E402
import tracing                                   # noqa: E402
from layer_metrics import (host_readers, named_readers,   # noqa: E402
                           span_readers)

_build = cluster.build
_drain = cluster.Served.drain
_run = drive.Run.run
_load = tracing.load
_before = {}

HOST = (("host_cpu_ms_per_tick", host_readers.host_cpu_ms_per_tick),
        ("host_off_cpu_ms_per_tick", host_readers.host_off_cpu_ms_per_tick),
        ("fetch_cpu_ms_per_tick", lambda ctx, tier:
            host_readers.phase_cpu_ms_per_tick(ctx, tier, "fetch")),
        ("runqueue_wait_ms_per_tick", host_readers.runqueue_wait_ms_per_tick))
EDGE = (("awake_cpu_ms_per_tick", host_readers.edge_awake_cpu_ms_per_tick),
        ("tokens_per_wakeup", host_readers.tokens_per_wakeup),
        ("wake_lag_ms_mean", lambda ctx, tier: span_readers.histogram_mean(
            ctx, "dllm_edge_wake_lag_ms", tier)),
        ("wakeups", lambda ctx, tier: span_readers._delta(
            ctx, "dllm_edge_wakeups_total", tier=tier)))


def edge_causes(doc, tier):
    """Awake slices of the tier's edge lanes by the scheduler phase in
    whose self-time their first token was stamped (``none``: under no
    slice the ring still holds); slices that took no token left out."""
    origin = (doc.get("metadata") or {}).get("ts_origin_perf_counter_s")
    slices = span_readers.tier_slices(doc, tier)
    if origin is None or not slices:
        return None
    phases = span_readers.self_intervals(slices)
    lanes = host_readers.edge_lane_tids(doc, tier)
    stamps = sorted(origin + (e["ts"] - 1e3 * e["args"]["wake_lag_ms"]) / 1e6
                    for e in doc["traceEvents"] if e["ph"] == "X"
                    and e["tid"] in lanes and "wake_lag_ms" in e["args"])
    out, i = {}, 0
    for t in stamps:
        while i < len(phases) and phases[i][2] < t - 1e-6:
            i += 1
        hit = i < len(phases) and phases[i][1] - 1e-6 <= t
        name = phases[i][0] if hit else "none"
        out[name] = out.get(name, 0) + 1
    return out


def phase_split(ctx, tier):
    """Self wall | self CPU milliseconds a decode tick of every phase
    the scheduler stamped in the run."""
    phases = sorted({lab["phase"] for lab, _ in span_readers._series(
        ctx.metrics_after, host_readers.CPU) if lab.get("tier") == tier})
    return {p: [round(host_readers._per_tick(ctx, tier, fam, phase=p) or 0.0,
                      4) for fam in (host_readers.WALL, host_readers.CPU)]
            for p in phases}


def chunk_programs(trace):
    """Whole ``jit_chunk_prefill`` executions of every device by compiled
    program, shortest first: ``[fingerprint, executions, mean ms, least,
    most]``.  The engine compiles one chunk program a window rung, so
    with one chunk width the rows ARE the rungs, in order."""
    by_name = {}
    for dev in trace["devices"].values():
        for m in dev["modules"]:
            if m[0].startswith("jit_chunk_prefill("):
                by_name.setdefault(m[0], []).append(m)
    rows = []
    for name, modules in by_name.items():
        ms = [d / 1e6 for _, d in named_readers.executions(
            {"modules": modules}, "chunk_prefill",
            trace["t_lo"], trace["t_hi"])]
        if ms:
            rows.append([name[name.index("(") + 1:-1], len(ms),
                         round(sum(ms) / len(ms), 3), round(min(ms), 3),
                         round(max(ms), 3)])
    return sorted(rows, key=lambda r: r[2])


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = .*op_name=\"([^\"]*)\"")


def tick_scopes(engine):
    """Per warmed rung of the engine's decode tick (its table window in
    tokens): ``{operation: in the attention scope?}`` for every
    instruction of the compiled program that a device trace can show,
    those outside any fusion — the rung's own program, compiled again
    against the pool's shapes (the persistent compile cache has it)."""
    import chip_smoke
    import jax
    bs = engine.paged.block_size
    pool = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        engine.pool)
    out = {}
    for wb, _ in sorted(engine._compiled.get("decode", ())):
        (compiled, _), = chip_smoke.pool_programs(
            engine, pool, [wb * bs]).values()
        ops, fused = {}, False
        for line in compiled.as_text().splitlines():
            if line.endswith("{") and " = " not in line:
                fused = "fused_computation" in line
            m = None if fused else _HLO_OP.match(line)
            if m:
                ops[m[1]] = "/attention/" in m[2]
        out[wb * bs] = ops
    return out


def attention_by_rung(trace, scopes, steps):
    """``{rungs: {"ticks", "ms_a_step", "by_op_ms_a_step"}}``: the
    attention scope's operations inside the whole ``jit_decode_tick``
    executions of the trace, each compiled program matched to the rung
    (or rungs, ``"128|256"``) whose HLO names the most of its
    operations."""
    rows = {}
    for dev in trace["devices"].values():
        by_program = {}
        for m in dev["modules"]:
            if m[0].startswith("jit_decode_tick("):
                by_program.setdefault(m[0], []).append(m)
        in_time = sorted(dev["ops"], key=lambda e: e[1])
        for modules in by_program.values():
            spans = named_readers.executions(
                {"modules": modules}, "decode_tick",
                trace["t_lo"], trace["t_hi"])
            if not spans:
                continue
            inside, i = {}, 0
            for name, start, dur in in_time:
                while i < len(spans) and sum(spans[i]) < start:
                    i += 1
                if i < len(spans) and spans[i][0] <= start:
                    name = name.split("@")[0]
                    inside[name] = inside.get(name, 0) + dur
            named = {r: len(inside.keys() & scopes[r]) for r in scopes}
            rungs = [r for r in scopes if named[r] == max(named.values())]
            rung = rungs[-1]
            per_step = 1e6 * len(spans) * steps
            ops = {n: round(d / per_step, 4) for n, d in inside.items()
                   if scopes[rung].get(n)}
            # Rungs whose programs name their operations alike cannot be
            # told apart in a trace: the row is filed under all of them.
            rows["|".join(map(str, rungs))] = {
                          "ticks": len(spans),
                          "ms_a_step": round(sum(ops.values()), 4),
                          "by_op_ms_a_step": dict(sorted(
                              ops.items(), key=lambda kv: -kv[1])[:8])}
    return rows


def _load_and_print(path):
    trace = _load(path)
    print("[bench:stats] chunk_prefill executions by program "
          "[fingerprint, n, mean_ms, min_ms, max_ms] = "
          + json.dumps(chunk_programs(trace)), flush=True)
    served = _before.get("served")
    for name in (served.entries if served else ()):
        engine = served.engine(name)
        if not getattr(engine, "_compiled", {}).get("decode"):
            continue
        rows = attention_by_rung(trace, tick_scopes(engine),
                                 engine.steps_per_tick)
        for rungs, row in rows.items():
            row["form"] = engine.decode_attention_form(
                int(rungs.split("|")[-1]))
        print(f"[bench:stats] tiers.{name}.attention device ms a step by "
              f"rung = {json.dumps(rows)}", flush=True)
    return trace


def _build_and_keep(*args, **kw):
    _before["served"] = _build(*args, **kw)
    return _before["served"]


def _run_after_metrics(self, *args, **kw):
    _before["metrics"] = self.client.get("/metrics").text
    return _run(self, *args, **kw)


def _drain_after_stats(self) -> None:
    try:
        tiers = self.get_json("/stats").get("tiers", {})
        ctx = types.SimpleNamespace(
            metrics_before=_before.get("metrics", ""),
            metrics_after=self.client.get("/metrics").text)
        for name in self.entries:
            block = tiers.get(name, {})
            # "state": the recurrent rows' mixer (mamba2, mamba1, cca_tail,
            # kda), their count and bytes, and the K/V or latent layers
            # beside them; null for a model without rows.
            for key in ("tick", "prefill", "state"):
                print(f"[bench:stats] tiers.{name}.{key} = "
                      f"{json.dumps(block.get(key))}", flush=True)
            print(f"[bench:stats] tiers.{name}.moe.grouped_product = "
                  + json.dumps((block.get("moe") or {}).get(
                      "grouped_product")), flush=True)
            for key, rows in (("host", HOST), ("edge", EDGE)):
                print(f"[bench:stats] tiers.{name}.{key} = " + json.dumps(
                    {k: fn(ctx, name) for k, fn in rows}), flush=True)
            print(f"[bench:stats] tiers.{name}.phase_wall_cpu = "
                  + json.dumps(phase_split(ctx, name)), flush=True)
            print(f"[bench:stats] tiers.{name}.edge_causes = " + json.dumps(
                edge_causes(self.get_json("/debug/trace"), name)),
                flush=True)
    finally:
        _drain(self)


cluster.build = _build_and_keep
cluster.Served.drain = _drain_after_stats
drive.Run.run = _run_after_metrics
tracing.load = _load_and_print
sys.argv = [os.path.join("benchmark", "run.py")] + sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
