#!/usr/bin/env python3
"""The latent chunk attention alone at the shapes the three latent cells
warm and run: the kernel of ``ops/latent_chunk_attention.py`` against the
plain form beside it (``latent_chunk_attention.plain``: the up-projection
of the whole window, ``einsum`` + ``softmax``), the two that
``models/latent_moe.py`` ``_attend`` chooses between,
on the chip, in one process.

    python3 scripts/latent_chunk_attention_shapes.py [--rehearse] [--tune]

A shape is (heads, queries, window, the chunk's end): the queries sit at
positions ``end - queries .. end - 1`` of the window, so ``end`` < window
is a chunk whose rung is wider than what it has written (the kernel skips
the rest, the plain form does not).  Each form runs ``--calls`` calls
chained through the queries inside ONE program, so what is timed is the
device and not the host's dispatch; the median of ``--reps`` timings, as
microseconds a call, beside the call's matrix products over the chip's
197 TFLOP/s (the live window's: what the kernel has to multiply) and what
``serves`` says of the shape.  ``--tune`` also times the kernel at other
block sizes than ``blocking`` gives (the module's caps patched, for this
script's process alone).  Prints one JSON line a reading and writes them
to ``chiprun_out/latent_chunk_attention_shapes.jsonl``.  ``--rehearse``
(small shapes, any backend) shows only that the script runs: a time read
off the chip is not a time.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12               # v5e, bfloat16 (Google Cloud documentation)
DC, DN, DR, DV, ROW = 512, 128, 64, 128, 640
# (label, heads, queries, window, end).  The lane's programs of the three
# cells, a half-written top rung, xing's reuse suffixes.
SHAPES = [
    ("sarvam lane 256", 64, 256, 256, 256),
    ("sarvam lane 1024", 64, 256, 1024, 1024),
    ("sarvam lane 2048", 64, 256, 2048, 2048),
    ("sarvam lane 4096", 64, 256, 4096, 4096),
    ("sarvam lane 8192", 64, 256, 8192, 8192),
    ("sarvam lane 16384", 64, 256, 16384, 16384),
    ("sarvam lane 16384 half written", 64, 256, 16384, 8448),
    ("sarvam lane 16640", 64, 256, 16640, 16640),
    ("xing lane 256", 32, 256, 256, 256),
    ("xing lane 1024", 32, 256, 1024, 1024),
    ("xing lane 2048", 32, 256, 2048, 2048),
    ("xing lane 4096", 32, 256, 4096, 4096),
    ("xing lane 8192", 32, 256, 8192, 8192),
    ("kimi lane 5120", 32, 256, 5120, 5120),
    ("xing reuse 64 over 256", 32, 64, 256, 256),
    ("xing reuse 64 over 1024", 32, 64, 1024, 1024),
    ("xing reuse 64 over 8192", 32, 64, 8192, 8192),
    ("xing reuse 128 over 8192", 32, 128, 8192, 8192),
]
# (heads a step, window rows a block, window rows a piece, heads written
# out a pass) caps to try.
TUNE = [(8, 2048, sub, unroll) for sub in (512, 1024, 2048)
        for unroll in (1, 2, 4, 8)]
TUNE_SHAPES = ("sarvam lane 256", "sarvam lane 1024", "sarvam lane 16384",
               "sarvam lane 16384 half written", "xing lane 1024",
               "xing lane 4096")


def flops(n: int, s: int, live: int) -> float:
    """The matrix products over the ``live`` window rows: the
    up-projection, the scores, the values."""
    return 2.0 * n * live * (DC * (DN + DV) + s * (DN + DR) + s * DV)


def make_args(n: int, s: int, w: int, end: int, widths):
    import jax
    import jax.numpy as jnp
    dc, dn, dr, dv, row = widths
    keys = jax.random.split(jax.random.PRNGKey(n + s + w + end), 4)
    bf16 = jnp.bfloat16
    return (jax.random.normal(keys[0], (1, s, n, dn), bf16),
            jax.random.normal(keys[1], (1, s, n, dr), bf16),
            jax.random.normal(keys[2], (1, w, row), bf16),
            (jax.random.normal(keys[3], (dc, n, dn + dv)) * dc ** -0.5
             ).astype(bf16),
            (end - s + jnp.arange(s, dtype=jnp.int32))[None])


def time_form(form, args, calls: int, reps: int) -> float:
    """Microseconds a call: ``calls`` calls in one program, each one's
    queries moved by a hair of the one before's output."""
    import jax

    @jax.jit
    def chained(q_nope, *rest):
        def body(_, q):
            return q + form(q, *rest) * 1e-3
        return jax.lax.fori_loop(0, calls, body, q_nope)
    jax.block_until_ready(chained(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / calls * 1e6


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--tune", action="store_true")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import functools

    import jax
    import numpy as np

    from distributed_llm_tpu.ops import latent_chunk_attention as LCA

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): --rehearse runs the script "
              f"without one", file=sys.stderr)
        return 3
    shapes, widths, calls, tune = SHAPES, (DC, DN, DR, DV, ROW), args.calls, TUNE
    if args.rehearse:
        shapes = [("rehearsal", 4, 32, 512, 384)]
        widths, calls, tune = (128, 128, 64, 128, 256), 2, [(2, 256, 128, 2)]
    dc, dn, dr, dv, row = widths
    scale = (dn + dr) ** -0.5
    lines = []

    def read(line, form, operands, want=None):
        try:
            got = np.asarray(jax.jit(form)(*operands), np.float32)
            if want is not None:
                line["max_abs_diff"] = float(np.max(np.abs(got - want)))
            line["us_a_call"] = time_form(form, operands, calls, args.reps)
            line["share_of_peak"] = (line["flops_live"] / PEAK_FLOPS
                                     / line["us_a_call"] * 1e6)
        except Exception as e:                       # a form the compiler
            line["error"] = str(e)[:400]             # refuses
            got = None
        print(json.dumps(line), flush=True)
        lines.append(line)
        return got

    for label, n, s, w, end in shapes:
        operands = make_args(n, s, w, end, widths)
        base = {"shape": label, "heads": n, "queries": s, "window": w,
                "end": end, "device": f"{dev.platform}:{dev.device_kind}",
                "calls": calls, "flops_live": flops(n, s, end),
                "serves": LCA.serves(s, w, n, dn, dr, dv, dc, row,
                                     "bfloat16"),
                "plain_temporaries_bytes": LCA.plain_temporaries_bytes(
                    s, w, n, dn, dv, 2)}
        want = read({**base, "form": "plain"},
                    functools.partial(LCA.plain, scale=scale), operands)
        kernel = functools.partial(LCA.latent_chunk_attention, scale=scale)
        read({**base, "form": "blocks", "blocking": LCA.blocking(s, w, n)},
             kernel, operands, want)
        if not (args.tune and (label in TUNE_SHAPES or args.rehearse)):
            continue
        caps = (LCA.MAX_HEADS_A_STEP, LCA.MAX_BLOCK_ROWS, LCA.MAX_SUB_ROWS,
                LCA.HEADS_UNROLLED)
        for trial in tune:
            (LCA.MAX_HEADS_A_STEP, LCA.MAX_BLOCK_ROWS, LCA.MAX_SUB_ROWS,
             LCA.HEADS_UNROLLED) = trial
            jax.clear_caches()
            read({**base, "form": "blocks", "unrolled": trial[3],
                  "blocking": LCA.blocking(s, w, n), "tuned": True},
                 kernel, operands, want)
        (LCA.MAX_HEADS_A_STEP, LCA.MAX_BLOCK_ROWS, LCA.MAX_SUB_ROWS,
         LCA.HEADS_UNROLLED) = caps
        jax.clear_caches()

    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "latent_chunk_attention_shapes.jsonl"),
              "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
