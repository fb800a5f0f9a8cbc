#!/usr/bin/env python3
"""Compile a configuration's step programs for a DESCRIBED v5e:2x2.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_check.py --config smollm2-1.7b

No chip is attached: nothing runs, so this gives program sizes and what
the chip's compiler refuses, never a time.  For each tier of the
configuration it builds the program's own engine on shapes
(``jax.eval_shape`` weights, a token pool) with ``jax.default_backend``
answering "tpu", and lowers the engine's OWN decode tick (every window
rung the configuration's buckets give), chunk-prefill and cold-prefill
programs against the real weights' and pool's shapes.  Run before the
first chip call of a new configuration.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    sys.path.insert(0, p)

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402

import cluster                                         # noqa: E402
import manifest as mf                                  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--tiers", default=None, help="comma list; all if unset")
    args = ap.parse_args()
    config = mf.load_json("configs", args.config + ".json")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    from distributed_llm_tpu import models
    from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    from distributed_llm_tpu.engine.paged_kv import PagedConfig, init_pool
    from distributed_llm_tpu.ops import (attention, pallas_attention,
                                         ragged_attention)
    # The engine and the kernels ask jax which backend runs them; the
    # answer for the described chip is "tpu", compiled kernels.
    jax.default_backend = lambda: "tpu"
    attention._DISPATCH_TABLE = attention._DISPATCH_META = None
    pallas_attention._interpret = lambda: False
    ragged_attention._interpret = lambda: False

    cursor = 0
    for name, e in cluster.tier_entries(config, False).items():
        tp = int(e["tier"].get("tp", 1))
        devs = list(topo.devices[cursor:cursor + tp])
        cursor += tp
        if args.tiers and name not in args.tiers.split(","):
            continue
        if tp > 1:
            # The engine commits its pool to its mesh as it is built, and
            # a described mesh can hold no array: its programs cannot be
            # lowered this way.  PR 22 compiled the tp=2 family of
            # orin_8b (Mistral-7B's shapes but for the vocabulary) with a
            # script of its own.
            print(f"[compile:{name}] tp={tp}: skipped (the engine places "
                  f"its pool on its mesh when it is built)", flush=True)
            continue
        cfg = cluster.program_config(e)
        MODEL_PRESETS[e["preset"]] = cfg
        kw = dict(e["tier"])
        kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
        tier = TierConfig(name=name, model_preset=e["preset"], **kw)
        one = jax.sharding.SingleDeviceSharding(devs[0])

        def on(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one), tree)

        params = on(jax.eval_shape(partial(models.init_params, cfg, seed=0)))
        paged = PagedConfig(block_size=tier.kv_block_size,
                            max_slots=tier.decode_batch,
                            max_seq_len=cfg.max_seq_len,
                            pool_blocks=tier.kv_pool_blocks)
        pool = on(jax.eval_shape(lambda: init_pool(cfg, paged, "none")))
        # The engine's own pool is real host memory: keep it tiny.
        tiny = max(tier.prefill_buckets) // tier.kv_block_size + 2
        engine = ContinuousBatchingEngine(
            dataclasses.replace(tier, kv_pool_blocks=tiny), params=params)
        b, bs = tier.decode_batch, tier.kv_block_size
        mb = paged.blocks_per_slot

        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        def report(label, compiled):
            m = compiled.memory_analysis()
            text = compiled.as_text()
            print(f"[compile:{name}] {label}: args "
                  f"{m.argument_size_in_bytes / 1e9:.2f} GB, temp "
                  f"{m.temp_size_in_bytes / 1e9:.2f} GB, out "
                  f"{m.output_size_in_bytes / 1e9:.2f} GB; kernels "
                  f"{text.count('tpu_custom_call')}, all-reduce "
                  f"{text.count('all-reduce(')}", flush=True)

        try:
            print(f"[compile:{name}] {e['preset']} tp={tp} ragged="
                  f"{engine.ragged} attention={engine.cfg.attention_impl} "
                  f"pool {paged.num_blocks} blocks", flush=True)
            rungs = sorted({min(r // bs, mb) for r in engine._buckets}
                           | {mb})
            for wb in ([mb] if engine.ragged else rungs):
                engine._decode_fn = None
                report(f"decode tick, window {wb * bs}",
                       engine._decode_step().lower(
                           params, pool, arg((b, wb)), arg((b,)), arg((b,)),
                           arg((b,), jnp.float32),
                           arg((2,), jnp.uint32)).compile())
            c = engine.chunk_tokens
            for w in engine._chunk_windows:
                if w < c:
                    continue
                report(f"chunk prefill ({c}, {w})",
                       engine._chunk_prefill_fn(c, w).lower(
                           params, pool, arg((1, c)), arg((1,)), arg((1,)),
                           arg((mb,)), arg((2,), jnp.uint32),
                           arg((), jnp.float32)).compile())
            for bucket in [x for x in engine._buckets if x <= c]:
                report(f"cold prefill {bucket}",
                       engine._prefill_fn(bucket).lower(
                           params, arg((1, bucket)), arg((1,)),
                           arg((2,), jnp.uint32),
                           arg((), jnp.float32)).compile())
        finally:
            engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
