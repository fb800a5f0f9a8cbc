#!/usr/bin/env python3
"""The grouped product at the shapes the benchmark's routed cells run
(and ``wide-reasoning``'s as stored before PR 55):
the Pallas kernel (``ops/grouped_product.py``) against
``jax.lax.ragged_dot``, and an expert layer's whole FFN as ONE call
(``grouped_ffn``) against the chain of calls, on the chip, in one process.

    python3 scripts/grouped_product_shapes.py [--rehearse] [--sweep]

For each shape: rows sorted by group over the STACKED groups of which one
layer's are non-empty, as ``models/latent_moe.py`` hands them to
``_grouped``; an up product and a down product chained ``--pairs`` times
inside ONE program, so that what is timed is the device and not the
host's dispatch; the median of ``--reps`` timings; GB/s of the touched
groups' bytes as stored; the largest difference between the two
implementations' results.  ``--sweep`` also tries other DMA sizes of the
kernel.  Then the FFN both ways (``impl`` ``ffn.chain`` and
``ffn.fused``: the (gate,) up, activation and down of a layer, ``--pairs``
layers in one program; us an FFN, GB/s of ALL its touched matrices, the
largest difference between the two).  Prints one JSON line a reading and
writes them to ``chiprun_out/grouped_product_shapes.jsonl``.  ``--rehearse``
(tiny widths, any backend) shows only that the script runs: a rate read
off the chip is not a rate.
"""
import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: rows, layers, groups a layer, touched groups, rows in groups,
# (in, out) of the up product; the down product is its transpose.
SHAPES = {
    "wide-reasoning.tick": (96, 2, 64, 26, 48, (2688, 1920)),
    "reasoned-reply.tick": (32, 5, 64, 23, 32, (3584, 1024)),
    "wide-reasoning.chunk": (1536, 2, 64, 56, 768, (2688, 1920)),
    "reasoned-reply.chunk": (1024, 5, 64, 52, 1024, (3584, 1024)),
    # What the cell stored from PR 33 to PR 54 (multiples of 256), kept
    # beside what it stores since PR 55 (whole lane-widths): a tenth more
    # bytes a touched expert, the same rate.
    "wide-reasoning.tick.stored-by-256": (96, 2, 64, 26, 48, (2816, 2048)),
    "wide-reasoning.chunk.stored-by-256": (1536, 2, 64, 56, 768,
                                           (2816, 2048)),
    "context-reasoning.tick": (16, 20, 16, 7, 16, (2048, 2048)),
    "context-reasoning.chunk": (256, 20, 16, 16, 256, (2048, 2048)),
}
# The cells whose experts have no gate (``relu2``).
UNGATED = "wide-reasoning"


def sizes_for(rng, layers, per_layer, touched, rows_in, layer):
    """``rows_in`` rows over ``touched`` of layer ``layer``'s groups, at
    least one each, skewed as routing is."""
    import numpy as np
    sizes = np.zeros(layers * per_layer, np.int32)
    ids = layer * per_layer + rng.choice(per_layer, touched, replace=False)
    sizes[ids] = 1
    weights = rng.dirichlet(np.full(touched, 0.7))
    for g in rng.choice(ids, rows_in - touched, p=weights):
        sizes[g] += 1
    return sizes


def median_seconds(run, args, reps: int) -> float:
    """The median of ``reps`` timings of ``run(*args)``, after one call
    that compiles it."""
    import jax
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--pairs", type=int, default=12)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_tpu.ops import grouped_product as GP

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): --rehearse runs the script "
              f"without one", file=sys.stderr)
        return 3
    variants = {"kernel": {}}
    if args.sweep:
        variants.update({
            "kernel.dma3": {"tile_bytes": 3 << 20},
            "kernel.dma16": {"tile_bytes": 16 << 20},
        })
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = []
    rng = np.random.default_rng(34)
    for name, (rows, layers, per, touched, rows_in, (k, n)) in SHAPES.items():
        if args.only and args.only not in name:
            continue
        if args.rehearse:
            k, n, layers = 128, n // 16 // 128 * 128 or 128, 2
        groups = layers * per
        sizes = jnp.asarray(sizes_for(rng, layers, per, touched, rows_in,
                                      layers // 2))
        keys = jax.random.split(jax.random.PRNGKey(34), 3)
        x = jax.random.normal(keys[0], (rows, k), jnp.float32
                              ).astype(jnp.bfloat16)

        @functools.partial(jax.jit, static_argnums=(1, 2))
        def matrices(key, k, n):        # no float32 copy of 2.7 GB
            return (jax.random.normal(key, (groups, k, n), jnp.float32)
                    * k ** -0.5).astype(jnp.bfloat16)

        up = down = gate = None         # the shape before's, freed
        up, down = matrices(keys[1], k, n), matrices(keys[2], n, k)
        touched_bytes = 2 * touched * k * n * 2      # up + down, bfloat16

        def program(product):
            def run(h, up, down, sizes):
                def pair(_, h):
                    a = product(h, up, sizes)
                    return product(jax.nn.relu(a), down, sizes)
                return jax.lax.fori_loop(0, args.pairs, pair, h)
            return jax.jit(run)

        impls = {"ragged_dot": jax.lax.ragged_dot}
        impls.update({v: functools.partial(GP.grouped_product, **kw)
                      for v, kw in variants.items()})
        want = None
        for impl, product in impls.items():
            line = {"shape": name, "rows": rows, "groups": groups,
                    "touched": touched, "in": k, "out": n, "impl": impl,
                    "device": f"{dev.platform}:{dev.device_kind}",
                    "serves": GP.serves(rows, groups, k, n, jnp.bfloat16)}
            try:
                got = np.asarray(jax.jit(product)(x, up, sizes),
                                 np.float32)[:rows_in]
                if want is None:
                    want = got
                line["max_abs_diff_to_ragged_dot"] = float(
                    np.max(np.abs(got - want)))
                line["max_abs"] = float(np.max(np.abs(want)))
                per_pair = median_seconds(
                    program(product), (x, up, down, sizes),
                    args.reps) / args.pairs
                line["ms_a_product"] = per_pair / 2 * 1e3
                line["gb_per_s_touched"] = touched_bytes / per_pair / 1e9
            except Exception as e:                   # a variant the
                line["error"] = str(e)[:400]         # compiler refuses
            print(json.dumps(line), flush=True)
            lines.append(line)

        # The layer's whole FFN: the chain of calls (what the models ran
        # before PR 53, and run where the one call does not serve), and
        # the one call.
        gated = UNGATED not in name
        gate = (matrices(jax.random.fold_in(keys[1], 1), k, n) if gated
                else None)

        def chain(h, gate, up, down, sizes):
            u = GP.grouped_product(h, up, sizes)
            a = (GP.activation(u) if gate is None else
                 GP.activation(GP.grouped_product(h, gate, sizes), u))
            return GP.grouped_product(a, down, sizes)

        def layers_of(ffn):
            def run(x, gate, up, down, sizes):
                def layer(_, h):        # a residual keeps the numbers sane
                    return x + ffn(h, gate, up, down, sizes) * 0.125
                return jax.lax.fori_loop(0, args.pairs, layer, x)
            return jax.jit(run)

        ffns = {"ffn.chain": chain, "ffn.fused": GP.grouped_ffn}
        want = None
        for impl, ffn in ffns.items():
            line = {"shape": name, "rows": rows, "groups": groups,
                    "touched": touched, "in": k, "F": n, "gated": gated,
                    "impl": impl,
                    "device": f"{dev.platform}:{dev.device_kind}",
                    "serves_ffn": GP.serves_ffn(rows, groups, k, n, k,
                                                jnp.bfloat16, gated)}
            try:
                got = np.asarray(jax.jit(ffn)(x, gate, up, down, sizes),
                                 np.float32)
                if want is None:
                    want = got
                line["max_abs_diff_to_chain"] = float(
                    np.max(np.abs(got - want)))
                line["max_abs"] = float(np.max(np.abs(want)))
                per_ffn = median_seconds(
                    layers_of(ffn), (x, gate, up, down, sizes),
                    args.reps) / args.pairs
                line["us_an_ffn"] = per_ffn * 1e6
                line["gb_per_s_touched"] = ((2 + gated) * touched * k * n * 2
                                            / per_ffn / 1e9)
            except Exception as e:
                line["error"] = str(e)[:400]
            print(json.dumps(line), flush=True)
            lines.append(line)
    with open(os.path.join(out_dir, "grouped_product_shapes.jsonl"),
              "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
