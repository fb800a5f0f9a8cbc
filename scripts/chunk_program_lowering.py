#!/usr/bin/env python3
"""What a tree's chunk programs cost BEFORE the compile cache is asked:
the wall time of tracing and lowering every chunk program (and the cold
prefill of the warm-up request) that a benchmark configuration's engine
warms, for a DESCRIBED v5e, in the sandbox.

    JAX_PLATFORMS=cpu python3 scripts/chunk_program_lowering.py \
        [--tree <checkout>] [--configs xing4.0-29b-a4b,sarvam-105b,...]

A kernel whose body is traced at every call site of every program is paid
there, warm cache or cold: PR 60's latent chunk kernel cost 16 s of
``setup_s`` that way, and PR 47's first chunk scan 60 s.  ``--tree`` names
the checkout whose package and benchmark are imported (this one by
default): run it on the parent (``git archive``) and on the change and
compare the sums.  Nothing is compiled and nothing runs: a count of
seconds on the sandbox's CPU, which says how the two trees differ, not
what a chip's host will take.
"""
import argparse
import dataclasses
import json
import os
import sys
import time
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--configs", default="xing4.0-29b-a4b,sarvam-105b,"
                                         "kimi-linear-48b-a3b")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    for p in (os.path.join(tree, "benchmark"), tree):
        sys.path.insert(0, p)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    import cluster
    import manifest as mf
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    from distributed_llm_tpu import models
    from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    from distributed_llm_tpu.engine.paged_kv import PagedConfig, init_pool
    from distributed_llm_tpu.ops import attention, pallas_attention
    # As ``benchmark/tools/compile_check.py``: the described chip's answer.
    jax.default_backend = lambda: "tpu"
    attention._DISPATCH_TABLE = attention._DISPATCH_META = None
    pallas_attention._interpret = lambda: False
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def on(tree_):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree_)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    total = 0.0
    for name in args.configs.split(","):
        config = mf.load_json("configs", name + ".json")
        e = cluster.tier_entries(config, False)["nano"]
        cfg = cluster.program_config(e)
        MODEL_PRESETS[e["preset"]] = cfg
        kw = dict(e["tier"])
        kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
        tier = TierConfig(name="nano", model_preset=e["preset"], **kw)
        params = on(jax.eval_shape(partial(models.init_params, cfg, seed=0)))
        paged = PagedConfig(block_size=tier.kv_block_size,
                            max_slots=tier.decode_batch,
                            max_seq_len=cfg.max_seq_len,
                            pool_blocks=tier.kv_pool_blocks)
        pool = on(jax.eval_shape(lambda: init_pool(cfg, paged, "none")))
        tiny = max(tier.prefill_buckets) // tier.kv_block_size + 2
        engine = ContinuousBatchingEngine(
            dataclasses.replace(tier, kv_pool_blocks=tiny), params=params)
        try:
            mb, c = paged.blocks_per_slot, engine.chunk_tokens
            # ``warmup``'s programs, in its order.
            programs = []
            if engine.prefix_cache is not None:
                programs += [(sb, w) for sb in engine._reuse_buckets
                             for w in engine._reuse_windows if w >= sb + 1]
            programs += [(c, w) for w in engine._chunk_windows if w >= c]
            seen, config_s = set(), 0.0
            for chunk, window in programs:
                if (chunk, window) in seen:
                    continue
                seen.add((chunk, window))
                form = getattr(engine, "chunk_attention_form",
                               lambda *a: None)(chunk, window)
                t0 = time.perf_counter()
                engine._chunk_prefill_fn(chunk, window).lower(
                    params, pool, arg((1, chunk)), arg((1,)), arg((1,)),
                    arg((mb,)), arg((2,), jnp.uint32), arg((), jnp.float32))
                took = time.perf_counter() - t0
                config_s += took
                print(json.dumps({"config": name, "program": "chunk_prefill",
                                  "chunk": chunk, "window": window,
                                  "attention_form": form,
                                  "lower_s": round(took, 3)}), flush=True)
            if not cfg.hybrid:
                bucket = min(engine._buckets)
                t0 = time.perf_counter()
                engine._prefill_fn(bucket).lower(
                    params, arg((1, bucket)), arg((1,)),
                    arg((2,), jnp.uint32), arg((), jnp.float32))
                took = time.perf_counter() - t0
                config_s += took
                print(json.dumps({"config": name, "program": "cold_prefill",
                                  "chunk": bucket, "lower_s": round(took, 3)}),
                      flush=True)
        finally:
            engine.stop()
        total += config_s
        print(json.dumps({"config": name, "programs": len(seen),
                          "lower_s_sum": round(config_s, 3)}), flush=True)
    print(json.dumps({"tree": tree, "lower_s_sum": round(total, 3)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
