#!/usr/bin/env python3
"""The served tick's attention at the windows the benchmark's cells run:
the table walked in the kernel (``ops/rows_attention.py``, the
``streamed`` form) against the XLA gather and
``merged_decode_attention`` (``merged``), on the chip, in one process.

    python3 scripts/decode_window_shapes.py [--rehearse] [--only NAME]

For each shape: a token-major pool ``[L, NB, bs, N_kv * D]`` carried
through a ``scan`` over its layers as the tick carries it — one row a
slot written, then the window attended, write before attend — so what is
timed is ``L`` layers of attention inside ONE program and not the host's
dispatch; live slots at positions drawn from the cell's traffic, idle
slots at position 0 over the trash block; the median of ``--reps``
timings, as microseconds a layer; the largest difference between the two
forms' results.  ``--blocks`` also tries other counts of table blocks a
grid step.  Prints one JSON line a reading and writes them to
``chiprun_out/decode_window_shapes.jsonl``.  ``--rehearse`` (tiny sizes,
any backend) shows only that the script runs: a time read off the chip is
not a time.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: layers, pool blocks, block, N_q, N_kv, D, slots, window, live
# slots, (lowest, highest) live position.
SHAPES = {
    # smollm2-1.7b.decode-closed: 8 replies of 128 tokens after 32-120.
    "decode-closed.256": (24, 145, 64, 32, 32, 64, 8, 256, 8, (40, 250)),
    # smollm2-1.7b.long-prompt: one client decodes, the other prefills.
    "long-prompt.2048": (24, 145, 64, 32, 32, 64, 8, 2048, 2, (1560, 1900)),
    # The same rung with every slot live at its end: the most it can cost.
    "full.2048": (24, 289, 64, 32, 32, 64, 8, 2048, 8, (1984, 2047)),
    "full.256": (24, 145, 64, 32, 32, 64, 8, 256, 8, (192, 255)),
    # nemotron-3-nano-30b-a3b.wide-reasoning: GQA 32/2 at head 128 (rows
    # of 512 B), 16 replies of 1024 after 296-464; its 2 attention layers
    # timed as 24, so that the host's dispatch is not what is read.
    "wide-reasoning.1024": (24, 1025, 64, 32, 2, 128, 16, 1024, 16,
                            (300, 1020)),
    "wide-reasoning.2048": (24, 1025, 64, 32, 2, 128, 16, 2048, 16,
                            (1030, 1480)),
    # nano_1b: GQA 32/8 at head 64 (rows of 1 KB).
    "nano_1b.256": (16, 513, 16, 32, 8, 64, 16, 256, 16, (40, 250)),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--blocks", action="store_true")
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_tpu.ops import attention as A
    from distributed_llm_tpu.ops import rows_attention as R

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): --rehearse runs the script "
              f"without one", file=sys.stderr)
        return 3
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "decode_window_shapes.jsonl"), "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    def program(form, layers, n_kv, d):
        def attend(q, kp, vp, tables, pos, layer):
            if form == "none":      # the scan, the write, the dispatch
                return q
            if form == "merged":
                k, v = A._gather_pool_seq(q, kp, vp, tables, None, None,
                                          layer, merged=True)
                return A.merged_decode_attention(q, k, v, pos)
            return R.paged_rows_decode_attention(q, kp, vp, tables, pos,
                                                 layer)

        def run(kp, vp, q, tables, pos):
            bs = kp.shape[2]
            blk = jnp.take_along_axis(tables, (pos // bs)[:, None], 1)[:, 0]

            def layer(carry, i):
                kp, vp, q = carry
                row = q.reshape(q.shape[0], -1)[:, :n_kv * d]
                kp = kp.at[i, blk, pos % bs].set(row)
                vp = vp.at[i, blk, pos % bs].set(row)
                out = attend(q, kp, vp, tables, pos, i)
                # The next layer's query depends on this one's result.
                return (kp, vp, (q + out * 0.01).astype(q.dtype)), None

            (kp, vp, q), _ = jax.lax.scan(layer, (kp, vp, q),
                                          jnp.arange(layers))
            return kp, vp, q
        return jax.jit(run, donate_argnums=(0, 1))

    for name, shape in SHAPES.items():
        if args.only and args.only not in name:
            continue
        layers, nb, bs, n_q, n_kv, d, slots, window, live, span = shape
        if args.rehearse:
            layers, nb, window = 2, 1 + slots * 4, 4 * bs
            span = (min(span[0], window - 2), min(span[1], window - 1))
        row, mb = n_kv * d, window // bs
        rng = np.random.default_rng(7)
        key = jax.random.PRNGKey(7)
        k0, k1, k2 = jax.random.split(key, 3)
        dtype = jnp.bfloat16
        tables = np.zeros((slots, mb), np.int32)
        pos = np.zeros(slots, np.int32)
        free = rng.permutation(np.arange(1, nb))
        for s in range(live):
            pos[s] = rng.integers(span[0], span[1] + 1)
            used = pos[s] // bs + 1
            tables[s, :used] = free[:used]
            free = free[used:]
        q = jax.random.normal(k0, (slots, n_q, d), dtype)
        tables, pos_d = jnp.asarray(tables), jnp.asarray(pos)
        results = {}
        variants = {"merged": None, "streamed": None, "none": None}
        if args.blocks:
            variants.update({f"streamed.g{g}": g for g in (1, 2, 4, 8)
                             if mb % g == 0})
        for label, g in variants.items():
            form = label.split(".")[0]
            if form == "streamed" and not R.serves(n_q, d, mb, bs, row, dtype):
                emit({"shape": name, "form": label, "served": False})
                continue
            keep = R.blocks_a_step
            if g is not None:
                R.blocks_a_step = lambda *a, g=g: g
            try:
                fn = program(form, layers, n_kv, d)
                times = []
                for rep in range(args.reps + 1):
                    kp = jax.random.normal(k1, (layers, nb, bs, row), dtype)
                    vp = jax.random.normal(k2, (layers, nb, bs, row), dtype)
                    jax.block_until_ready((kp, vp))
                    t0 = time.perf_counter()
                    kp, vp, out = fn(kp, vp, q, tables, pos_d)
                    jax.block_until_ready(out)
                    times.append(time.perf_counter() - t0)
                    del kp, vp
            finally:
                R.blocks_a_step = keep
            results[label] = np.asarray(out, np.float32)
            live_blocks = int(sum(p // bs + 1 for p in pos))
            emit({"shape": name, "form": label,
                  "blocks_a_step": g or R.blocks_a_step(mb, bs),
                  "us_a_layer": round(
                      statistics.median(times[1:]) / layers * 1e6, 2),
                  "us_a_layer_min": round(min(times[1:]) / layers * 1e6, 2),
                  "table_blocks": slots * mb, "live_blocks": live_blocks,
                  "block_bytes": bs * row * 2,
                  "max_abs_diff_vs_merged": float(np.abs(
                      results[label] - results["merged"]).max())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
