#!/usr/bin/env python3
"""The seed sweep behind a configuration's ``correct`` limit.

    python3 benchmark/tools/sweep_correct.py --config smollm2-1.7b \
        --seeds 24 --out chiprun_out/sweep-smollm2-1.7b.json

One process, one chip call per configuration, before any timed run.  For
every tier and every seed it reads the statistic of ``correct.py``, on
the configuration's own sample (``correct.sample``), against the float32
reference: the serving path at the stated precision (bfloat16 weights and
KV), the same seed again (it has to be bit-identical), and the controls
the configuration runs (``correct.controls``; both where it names none) —
the program's own int8 paths, the nearest precision below bfloat16, each
switched on ALONE: ``int8_weights`` (``quantize="int8"``) over a bfloat16
pool, and ``int8_kv``, bfloat16 weights over an int8 pool
(``kv_quantize="int8"``).  A limit has to lie above the largest
stated reading and under the smallest reading of every control it is
said to catch; a control the statistic does not separate by three times is
caught by ``correct.narrow_leaves`` instead, whose count the sweep reads
beside it.  Needs the TPU: exits 3 without one unless ``--rehearse``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    sys.path.insert(0, p)

import cluster                                         # noqa: E402
import correct                                         # noqa: E402
import manifest as mf                                  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=24)
    ap.add_argument("--first-seed", type=int, default=2147483659)
    ap.add_argument("--tiers", default=None)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    config = mf.load_json("configs", args.config + ".json")
    sample = correct.sample_sizes(config)
    controls = correct.controls_of(config)

    import jax
    import numpy as np
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    from distributed_llm_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    from distributed_llm_tpu import models
    from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
    from distributed_llm_tpu.engine.inference import upgrade_attention_impl
    from distributed_llm_tpu.engine.paged_kv import PagedConfig, init_pool

    # Seeds as the driver's: wider than 31 bits, odd steps apart.
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = {"config": args.config, "device": devices[0].device_kind,
           "platform": devices[0].platform, "seeds": seeds,
           "sample": {"lengths": list(sample[0]), "n_decode": sample[1]},
           "controls": list(controls), "tiers": {}}
    cursor = 0
    for name, e in cluster.tier_entries(config, args.rehearse).items():
        tp = int(e["tier"].get("tp", 1))
        devs = devices[cursor:cursor + tp]
        cursor += tp
        if args.tiers and name not in args.tiers.split(","):
            continue
        mesh = None
        if tp > 1:
            from distributed_llm_tpu.parallel.mesh import tp_mesh
            mesh = tp_mesh(list(devs), tp)
        cfg = upgrade_attention_impl(cluster.program_config(e), mesh)
        kw = dict(e["tier"])
        kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
        MODEL_PRESETS[e["preset"]] = cfg
        tier = TierConfig(name=name, model_preset=e["preset"], **kw)
        if mesh is not None:
            from distributed_llm_tpu.parallel.sharding import param_shardings
            sh = param_shardings(cfg, mesh)
        else:
            sh = jax.sharding.SingleDeviceSharding(devs[0])
        init = jax.jit(lambda s: models.init_params(cfg, s),
                       out_shardings=sh)
        # The reference sits on a chip the tiers leave free, if any.
        ref_dev = devices[-1] if len(devices) > 3 else devs[0]
        rows = []
        pcfg = PagedConfig(block_size=tier.kv_block_size, max_slots=1,
                           max_seq_len=tier.kv_block_size, pool_blocks=1)
        pool16 = jax.eval_shape(lambda: init_pool(cfg, pcfg, "none"))
        pool8 = (jax.eval_shape(lambda: init_pool(cfg, pcfg, "int8"))
                 if "int8_kv" in controls else None)
        common = dict(correct.tier_settings(tier, cfg), mesh=mesh,
                      ragged=False, n_decode=sample[1],
                      device=None if mesh is not None else devs[0])
        for seed in seeds:
            t0 = time.perf_counter()
            params = init(np.int32(seed % (2 ** 31)))
            seqs = correct.draw_sample(seed, cfg.vocab_size, sample[0])
            want = correct.reference_logits(e["family"], e["model"], seed,
                                            seqs, device=ref_dev,
                                            n_decode=sample[1])
            got = correct.system_logits(cfg, params, seqs,
                                        kv_quantize="none", **common)
            again = correct.system_logits(cfg, params, seqs,
                                          kv_quantize="none", **common)
            # Arrays stored in under 16 bits are counted beside each
            # reading: the stated precision has none, each control some.
            row = {"seed": seed,
                   "stated": correct.rel_frobenius(got, want),
                   "stated_again": correct.rel_frobenius(again, want),
                   "bit_identical": bool((got == again).all()),
                   "narrow_stated": len(correct.narrow_leaves(
                       {"params": params, "pool": pool16}))}
            del again
            if "int8_weights" in controls:
                params8 = correct.int8_weights(params, tier, cfg, mesh)
                w8 = correct.system_logits(cfg, params8, seqs,
                                           kv_quantize="none", **common)
                row["control_int8_weights"] = correct.rel_frobenius(w8, want)
                row["narrow_int8_weights"] = len(correct.narrow_leaves(
                    {"params": params8, "pool": pool16}))
                del params8, w8
            if "int8_kv" in controls:
                kv8 = correct.system_logits(cfg, params, seqs,
                                            kv_quantize="int8", **common)
                row["control_int8_kv"] = correct.rel_frobenius(kv8, want)
                row["narrow_int8_kv"] = len(correct.narrow_leaves(
                    {"params": params, "pool": pool8}))
                del kv8
            row["seconds"] = round(time.perf_counter() - t0, 2)
            rows.append(row)
            print(f"[sweep:{name}] {json.dumps(row)}", flush=True)
            del params, got, want
        summary = {"rows": rows, "all_bit_identical": all(
            r["bit_identical"] for r in rows)}
        for key in ("stated",) + tuple("control_" + c for c in controls):
            vals = [r[key] for r in rows]
            summary[key + "_min"], summary[key + "_max"] = min(vals), max(vals)
        out["tiers"][name] = summary
        print(f"[sweep:{name}] " + json.dumps(
            {k: v for k, v in summary.items() if k != "rows"}), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
