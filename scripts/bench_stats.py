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
``--trace 1``, the line of ``benchmark/layer_metrics/scope_readers.py``
for the tick and for the chunk program, whichever of the two the cell's
own metrics did not ask about: device time by ``jax.named_scope`` (the
program's own ``GET /debug/programs`` says which scope each traced
operation belongs to) and by window rung, each rung with its scopes;
beside it each of those programs' ``pool_sized_moves`` (PR 57: the same
route's count of the pool-sized arrays a program copies), and in every
run ``tiers.<tier>.pool``, each pool array's format at rest.

    python3 scripts/bench_stats.py --workload smollm2-1.7b.decode-closed \
        --seed 7 --seconds 50 --trace 0

From the root of a checkout, on the chip.  Nothing of ``benchmark/`` is
edited: ``cluster.Served.drain`` and ``drive.Run.run`` are wrapped in
this process only.
"""

import json
import os
import runpy
import sys
import types

ROOT = os.getcwd()
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import cluster                                   # noqa: E402
import drive                                     # noqa: E402
import layers                                    # noqa: E402
from layer_metrics import (host_readers, scope_readers,   # noqa: E402
                           span_readers)

_drain = cluster.Served.drain
_run = drive.Run.run
_breakdown = layers.breakdown
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


def _breakdown_after_scopes(ctx):
    """The reader's line for every tier's tick and chunk program (one a
    context: what a metric of the cell has read is not printed again),
    and the whole reduction, every operation's time and scope with it, in
    ``chiprun_out/scopes.<cell>.json``."""
    found = {f"{tier}.{program}": scope_readers.reduce_program(
        ctx, tier, program) for tier in ctx.served.entries
        for program in ("decode_tick", "chunk_prefill")}
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out",
                           f"scopes.{ctx.cell['name']}.json"), "w") as f:
        json.dump(found, f)
    for tier in ctx.served.entries:
        # Of the programs the readers asked GET /debug/programs about
        # (nothing is built for this line): the pool-sized arrays each
        # copies on its way in, round a loop or out; {} is none.
        moves = {f"{stage}:{'x'.join(map(str, key))}": built[
            "pool_sized_moves"] for (stage, key), built in sorted(
            ctx.served.engine(tier)._program_maps.items())}
        print(f"[bench:stats] tiers.{tier}.pool_sized_moves = "
              + json.dumps(moves), flush=True)
    return _breakdown(ctx)


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
            # kda; none for a pattern with no row kind), their count and
            # bytes, and the K/V or latent layers beside them; null for a
            # model without rows.
            # "pool": every pool array's format at rest (PR 57).
            for key in ("tick", "prefill", "state", "pool"):
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


cluster.Served.drain = _drain_after_stats
drive.Run.run = _run_after_metrics
layers.breakdown = _breakdown_after_scopes
sys.argv = [os.path.join("benchmark", "run.py")] + sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
