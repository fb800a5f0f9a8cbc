#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

One process that holds the cell's chips: it builds the cluster from the
cell's configuration file, makes the weights on the device from the seed,
warms the cell's shapes, decides ``correct``, drives the mix's traffic at
``/chat/stream`` from its own client threads for ``--seconds``, drains,
and prints one JSON object as the last line of its standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (a few seconds of the window under the profiler).

With no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result line.  ``--rehearse`` runs the same control flow at
the configuration's tiny rehearsal sizes on whatever jax finds (the CPU)
and prints ``rehearsal:`` lines, never a result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here
WALL_OFFSET = time.time() - T_PROCESS    # the program's timeline is in time.time()

import argparse
import json
import os
import sys
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest as mf                      # noqa: E402
from cluster import say                    # noqa: E402

TRACE_OFFSET_S = 2.0        # the traced part starts this far into the window
TRACE_SECONDS = 4.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no result line")
    return ap.parse_args(argv)


def find_devices(chips: int, rehearse: bool):
    import jax
    devices = jax.devices()
    d0 = devices[0]
    say("device", f"platform={d0.platform} device_kind={d0.device_kind!r} "
                  f"count={len(devices)}")
    if not rehearse and d0.platform != "tpu":
        print(f"benchmark: jax found no TPU (platform {d0.platform!r})",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), jax reports "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips]


class CompileCounter:
    """XLA compilations and persistent-cache loads, by jax's own
    monitoring events: inside the window both should count 0."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **kw):
        if event in self.EVENTS:
            self.n += 1
            self.names.append(str(kw.get("fun_name")))


def engine_programs(metrics_text: str) -> Dict[str, int]:
    """Programs the engines have minted, by tier and stage: the
    ``dllm_compiled_programs`` gauge of ``/metrics``."""
    out = {}
    for line in metrics_text.splitlines():
        if line.startswith("dllm_compiled_programs{"):
            label, value = line.rsplit(" ", 1)
            out[label[len("dllm_compiled_programs"):]] = int(float(value))
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    manifest = mf.load_manifest()
    cell = mf.find_cell(manifest, args.workload)
    config, mix = mf.cell_files(cell)
    e2e_specs = {m["name"]: mf.load_json("end_to_end", m["name"] + ".json")
                 for m in mf.metrics_for(manifest, cell["name"],
                                         "end_to_end")}
    layer_specs = {m["name"]: mf.load_json("layer_metrics",
                                           m["name"] + ".json")
                   for m in mf.metrics_for(manifest, cell["name"],
                                           "per_layer")}

    devices = find_devices(int(cell["chips"]), args.rehearse)
    from distributed_llm_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    say("device", f"compile cache: {enable_persistent_compile_cache()}")
    compiles = CompileCounter()

    import cluster
    import correct
    import drive
    import e2e
    import hoststalls
    import tracing
    from trafficgen import expand_grid

    sample = correct.sample_sizes(config)
    served = cluster.build(config, args.seed, args.rehearse, devices)
    try:
        lengths = sorted({it["tokens"] for it in expand_grid(mix)})
        for name in served.entries:
            cluster.warm_shapes(served, name, lengths)

        # -- correct: before t0, from nothing the traffic does ------------
        limits = config["correct"]["limit"]
        ref_device = (devices[-1] if len(devices) > len(
            {d.id for n in served.entries for d in served.tier_devices(n)})
            else None)
        ok = True
        for name, e in served.entries.items():
            t_c = time.perf_counter()
            stat = correct.engine_statistic(
                served.engine(name), e["family"], e["model"], args.seed,
                ref_device=ref_device, sample=sample)
            limit = limits.get(name)
            tier_ok = stat["finite"] and not stat["narrow"] and (
                args.rehearse or (limit is not None
                                  and stat["rel_err"] <= limit))
            ok = ok and tier_ok
            say("correct", f"tier {name}: logits rel. Frobenius error "
                           f"{stat['rel_err']!r} over {stat['positions']} "
                           f"positions, limit {limit}; arrays of the "
                           f"weights and the pool stored in under 16 "
                           f"bits: {len(stat['narrow'])} "
                           f"{stat['narrow'][:4]}, limit 0 -> "
                           f"{'ok' if tier_ok else 'NOT CORRECT'} "
                           f"({time.perf_counter() - t_c:.1f} s)")

        stats_before = served.get_json("/stats")
        metrics_before = served.client.get("/metrics").text
        programs_before = engine_programs(metrics_before)
        compiles_before = compiles.n

        # -- the timed window ----------------------------------------------
        run = drive.Run(served.client, mix, args.seed, args.seconds)
        traced: Dict[str, Any] = {}

        def in_window(t0: float) -> None:
            if not args.trace:
                return
            span = min(TRACE_SECONDS, max(0.5, args.seconds / 2))
            time.sleep(max(0.0, t0 + min(TRACE_OFFSET_S,
                                         args.seconds / 4)
                           - time.perf_counter()))
            with tracing.capture() as path:
                traced["path"] = path
                traced["host_lo"] = time.perf_counter()
                time.sleep(span)
                traced["host_hi"] = time.perf_counter()

        with hoststalls.recording() as stalls:
            run.run(in_window)
        t0 = run.t0
        setup_s = t0 - T_PROCESS
        stats_after = served.get_json("/stats?timeline=1")
        metrics_after = served.client.get("/metrics").text
        programs_after = engine_programs(metrics_after)
        n_programs = {k: v - programs_before.get(k, 0)
                      for k, v in programs_after.items()
                      if v != programs_before.get(k, 0)}
        n_compiles = compiles.n - compiles_before
        # Compilations after the window (none should come from the drain)
        # are in the count too; it is printed, not judged.

        due = e2e.in_window(run.records, t0, args.seconds)
        failed = [r for r in due if not r["ok"]]
        attempted = len(due) + run.unfinished
        n_failed = len(failed) + run.unfinished
        early = [r for r in due if r["ok"] and r["device"] in served.entries
                 and len(r["stamps"]) < served.entries[r["device"]]["tier"][
                     "max_new_tokens"]]
        mismatched = [r for r in due if r["ok"] and r["server"]
                      and r["server"]["tokens"] != len(r["stamps"])]
        say("window", f"{attempted} requests due in the window, "
                      f"{n_failed} failed ({run.unfinished} unfinished at "
                      f"the drain limit); {len(early)} ended early (EOS or "
                      f"PAD: traffic, not a fault); delta count differs "
                      f"from the done event's tokens in {len(mismatched)}")
        say("requests", "prompt_tokens:ttft_ms:tpot_ms " + " ".join(
            f"{r['prompt_tokens']}:{e2e.ttft_ms(r) or -1:.0f}:"
            f"{e2e.tpot_ms(r) or -1:.1f}" for r in sorted(
                due, key=lambda r: r["due"])[:80]))
        for r in failed[:5]:
            say("window", f"failed: {r['error']}")
        say("window", f"engine programs minted since warm-up: {n_programs}; "
                      f"XLA compilations or cache loads: {n_compiles} "
                      f"{compiles.names[len(compiles.names) - n_compiles:] if n_compiles else ''}")
        for who, line in hoststalls.summary(stalls, t0, args.seconds,
                                            WALL_OFFSET).items():
            say("host", f"{who}: {line}")
        # Client against server, per request: the client can only be later.
        worst = min((e2e.ttft_ms(r) - r["server"]["ttft_ms"]
                     for r in due if r["ok"] and r["server"]
                     and r["server"]["ttft_ms"] is not None
                     and e2e.ttft_ms(r) is not None), default=None)
        say("window", f"client TTFT minus the server's own ttft_ms, "
                      f"smallest over requests: {worst} ms (>= 0 expected)")

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(peak)}

        metrics: Dict[str, Dict[str, Any]] = {}
        breakdown = None
        end_to_end = {}
        for name, spec in e2e_specs.items():
            if name != "setup_s" and name not in mix["reports"]:
                continue
            value = e2e.compute(spec, run.records, t0, args.seconds, setup_s)
            if value is not None:
                end_to_end[name] = {"value": value, "unit": spec["unit"]}
        if not args.trace:
            metrics = end_to_end
        else:
            # Under the profiler these are not the cell's numbers: shown
            # on an earlier line only, to size the tracing overhead.
            say("traced", "end to end while traced: " + ", ".join(
                f"{k}={v['value']:.4g}" for k, v in end_to_end.items()))
            trace = tracing.load(traced["path"])
            tracing.discard(traced["path"])
            import layers
            ctx = layers.Context(
                cell=cell, config=config, mix=mix, served=served,
                records=run.records, t0=t0, seconds=args.seconds,
                stats_before=stats_before, stats_after=stats_after,
                metrics_before=metrics_before, metrics_after=metrics_after,
                wall_offset=WALL_OFFSET, trace=trace, host_span=(traced["host_lo"],
                                        traced["host_hi"]),
                peaks=layers.load_peaks(devices[0].device_kind,
                                        args.rehearse))
            for name, spec in layer_specs.items():
                fn = mf.load_callable(spec["reader"], "layer_metrics")
                value = fn(ctx, **spec.get("args", {}))
                if value is not None:
                    metrics[name] = {"value": value, "unit": spec["unit"]}
            busy, window_s = layers.device_busy(ctx)
            device["busy_s"], device["window_s"] = busy, window_s
            breakdown = layers.breakdown(ctx)
        for name, m in metrics.items():
            say("metric", f"{name} = {m['value']!r} {m['unit']}")

        result = {"correct": bool(ok), "attempted": attempted,
                  "failed": n_failed, "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
    finally:
        served.drain()
    if args.rehearse:
        print("rehearsal: " + json.dumps(
            {k: result[k] for k in ("correct", "attempted", "failed")}
            | {"metric_names": sorted(result["metrics"])}), flush=True)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
