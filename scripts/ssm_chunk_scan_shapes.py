#!/usr/bin/env python3
"""Mamba-1's chunk scan alone at the shape both row cells run it (256
positions x 16 states x 5120 channels, float32): the kernel of
``ops/ssm_chunk_scan.py`` against the form it had before PR 47, on the
chip, in one process.

    python3 scripts/ssm_chunk_scan_shapes.py [--rehearse]

Each form runs ``--calls`` calls chained through the state inside ONE
program (a ``fori_loop`` whose body is the custom call: ``y`` cannot be
dropped from a custom call, so every call does all its work), so what is
timed is the device and not the host's dispatch; the median of ``--reps``
timings, as microseconds a call, beside two floors: the bytes a call has
to move over the chip's 819 GB/s, and its register-wide operations over
the vector unit's issue rate.  The old form's spreads of ``B`` and ``C``
do not depend on the state, so XLA lifts them out of the loop: its number
is its kernel alone, as a trace reads it.  Prints one JSON line a reading
and writes them to ``chiprun_out/ssm_chunk_scan_shapes.jsonl``.
``--rehearse`` (a small shape, any backend) shows only that the script
runs: a time read off the chip is not a time.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9           # v5e (Google Cloud documentation)
# The vector unit of a v5e core: 4 issue slots of [8, 128] float32
# operations and one transcendental a cycle, at 1.5 GHz (197 TFLOP/s over
# 4 x 128 x 128 x 2).  A floor for the arithmetic, not a measurement.
VPU_SLOTS, CLOCK_HZ = 4, 1.5e9


def floors(t: int, n: int, inner: int) -> dict:
    """What a call cannot beat: its bytes (dt, u in; y out; a, the state
    in and out; B and C) at the memory's rate, and its register-wide
    operations (a state a position a register of 1024 channels: the
    decay's product, the chain's multiply and add, the feed's product,
    y's multiply and add; one ``exp``) at the unit's issue rate."""
    moved = 4 * (3 * t * inner + 3 * n * inner + 2 * t * n)
    registers = t * n * inner / 1024
    return {"bytes_moved": moved,
            "us_bytes_floor": moved / HBM_BYTES_PER_S * 1e6,
            "us_vector_floor": 6 * registers / VPU_SLOTS / CLOCK_HZ * 1e6,
            "us_exp_floor": registers / CLOCK_HZ * 1e6}


def before_pr47():
    """The kernel as PR 35 wrote it, kept HERE for the comparison alone:
    a grid step of 128 channels, the state ``[state, 128]``, a position a
    loop iteration, ``B`` and ``C`` spread over a lane width in VMEM, a
    sum across sublanes and a one-sublane store a position."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from distributed_llm_tpu.ops import pallas_attention

    def kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s_ref):
        a = a_ref[...]

        def step(t, state):
            dt = dt_ref[pl.ds(t, 1), :]
            state = (jnp.exp(dt * a) * state
                     + (dt * u_ref[pl.ds(t, 1), :]) * b_ref[t])
            y_ref[pl.ds(t, 1), :] = jnp.sum(state * c_ref[t], axis=0,
                                            keepdims=True)
            return state
        s_ref[...] = jax.lax.fori_loop(0, dt_ref.shape[0], step,
                                       s0_ref[...])

    def scan(dt, u, b, c, a, state):
        t, inner = dt.shape
        n = state.shape[0]
        spread = (t, n, 128)
        rows = pl.BlockSpec((t, 128), lambda i: (0, i))
        cols = pl.BlockSpec((n, 128), lambda i: (0, i))
        whole = pl.BlockSpec(spread, lambda i: (0, 0, 0))
        return pl.pallas_call(
            kernel, grid=(inner // 128,),
            in_specs=[rows, rows, whole, whole, cols, cols],
            out_specs=[rows, cols],
            out_shape=[jax.ShapeDtypeStruct((t, inner), jnp.float32),
                       jax.ShapeDtypeStruct((n, inner), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            name="ssm_chunk_scan_before_pr47",
            interpret=pallas_attention._interpret(),
        )(dt, u, jnp.broadcast_to(b[:, :, None], spread),
          jnp.broadcast_to(c[:, :, None], spread), a, state)
    return scan


def measure(forms: dict, shape, calls: int, reps: int, device: str) -> list:
    """A JSON line a form: µs a call, the largest difference to the first
    form's ``y`` and state, the floors."""
    import jax
    import numpy as np

    import chip_smoke
    t, n, inner = shape
    args = chip_smoke.kernel_cases(1, 1, 64, "float32", scan=shape)[
        "ssm_chunk_scan"].make_args()
    lines, want = [], None
    for name, scan in forms.items():
        line = {"form": name, "positions": t, "state": n, "inner": inner,
                "device": device, "calls": calls, **floors(t, n, inner)}
        try:
            got = [np.asarray(x) for x in jax.jit(scan)(*args)]
            want = want or got
            line["max_abs_diff_y"], line["max_abs_diff_state"] = (
                float(np.max(np.abs(g - w))) for g, w in zip(got, want))

            @jax.jit
            def chained(dt, u, b, c, a, state, scan=scan):
                return jax.lax.fori_loop(
                    0, calls, lambda _, s: scan(dt, u, b, c, a, s)[1], state)
            jax.block_until_ready(chained(*args))
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(chained(*args))
                times.append(time.perf_counter() - t0)
            line["us_a_call"] = statistics.median(times) / calls * 1e6
            line["bytes_floor_share"] = (line["us_bytes_floor"]
                                         / line["us_a_call"])
        except Exception as e:                       # a form the compiler
            line["error"] = str(e)[:400]             # refuses
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args()

    import jax

    import chip_smoke
    from distributed_llm_tpu.ops import ssm_chunk_scan as SC

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): --rehearse runs the script "
              f"without one", file=sys.stderr)
        return 3
    shape, calls = chip_smoke.SCAN_SHAPE, args.calls
    if args.rehearse:
        shape, calls = (16, 16, 1024), 2
    print(json.dumps({"shape": shape, "serves": SC.serves(*shape),
                      "lane_widths": SC.lane_widths(shape[2])}), flush=True)
    lines = measure({"before_pr47": before_pr47(),
                     "kernel": SC.ssm_chunk_scan},
                    shape, calls, args.reps,
                    f"{dev.platform}:{dev.device_kind}")
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "ssm_chunk_scan_shapes.jsonl"),
              "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
