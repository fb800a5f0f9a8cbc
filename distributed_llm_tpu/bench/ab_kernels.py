"""A/B the attention kernel implementations on the current backend.

Two modes::

    # End-to-end: full engine, TTFT + decode tok/s per DLLM_ATTENTION
    python -m distributed_llm_tpu.bench.ab_kernels [--tier nano|orin]
        [--prompt-tokens N] [--max-new N] [--repeat K]

    # Per-kernel micro A/B at serving shapes; optionally write the
    # measured dispatch table ops/attention.py consults (per-shape
    # dispatch instead of a blanket env pin)
    python -m distributed_llm_tpu.bench.ab_kernels micro
        [--tier nano|orin] [--repeat K] [--write-dispatch]

``micro`` times each kernel kind (prefill / decode / chunk / paged_decode)
directly — xla vs pallas, jitted, median of K — across the cache-length
ladder and serving batch sizes, at worst-case positions (full-length
frontier) so a pallas win is robust.  ``--write-dispatch`` publishes
``bench/ab_dispatch.json``: per kind, per length, the faster impl.

The engines are built sequentially in ONE process (one process holds
the chip); DLLM_ATTENTION is read at trace time, so each engine is
constructed after the env var is set and dropped before the next.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

DISPATCH_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "ab_dispatch.json")

# Every case class micro_ab can measure (--kinds validates against it) —
# derived from the serving ops' own dispatch-kind registry so the A/B
# grid and the dispatching wrappers can never cover different kernel
# sets (tests/test_kernel_dispatch.py pins the equality).
from ..ops.attention import DISPATCH_KINDS

ALL_KINDS = frozenset(DISPATCH_KINDS)


def _time_fn(fn, args, repeat: int):
    """(median wall ms, output) of a jitted call (2 warmup calls compile
    + settle).  The output feeds the numerics gate — timing alone would
    let a kernel that miscompiles on real Mosaic (interpreter-mode tests
    can't see that) win the table and serve wrong results."""
    import jax
    out = None
    for _ in range(2):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1000.0)
    return statistics.median(times), out


def micro_ab(tier_name: str = "orin", repeat: int = 20,
             write_dispatch: bool = False, fast: bool = False,
             beat=None, kinds=None) -> dict:
    """Direct kernel A/B at serving shapes; returns (and optionally
    publishes) the per-(kind, length) winner table.

    ``fast`` trims the grid to one mid-ladder length + the model max,
    batches 1/8.  ``beat`` is called after every case (a caller's idle
    watchdog counts it as liveness).  ``kinds`` (an iterable of kind names) restricts the grid
    — used to isolate or exclude a case class (r3: the grid hung on its
    decode_q8@1024 case)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..config import bench_cluster, tiny_cluster
    from ..ops import attention as A
    from ..ops import pallas_attention as PA
    from ..ops import ragged_attention as RA

    if kinds is not None:
        unknown = set(kinds) - ALL_KINDS
        if unknown:
            raise ValueError(f"unknown kinds {sorted(unknown)}; "
                             f"valid: {sorted(ALL_KINDS)}")

    cluster = (tiny_cluster() if jax.default_backend() == "cpu"
               else bench_cluster())
    cfg = getattr(cluster, tier_name).model()
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lengths = sorted({c for c in (256, 1024) if c < cfg.max_seq_len}
                     | {cfg.max_seq_len})
    batches = (1, 4, 8)
    if fast:
        lengths = sorted({min(1024, cfg.max_seq_len), cfg.max_seq_len})
        batches = (1, 8)
    key = jax.random.PRNGKey(0)
    bf16 = jnp.bfloat16
    results: dict = {"backend": jax.default_backend(), "model": cfg.name,
                     "repeat": repeat, "cases": []}
    wins: dict = {}

    def want(kind: str) -> bool:
        return kinds is None or kind in kinds

    def record(kind, length, fn_xla, args_xla, fn_pallas, args_pallas,
               detail):
        """Time both legs; a leg that RAISES (e.g. a Mosaic compile
        failure on new hardware) loses with ms=None instead of aborting
        the whole A/B — the dispatch table must still be written."""
        import jax as _jax

        if not want(kind):
            return

        def leg(fn, args):
            try:
                ms, out = _time_fn(_jax.jit(fn), args, repeat)
                return ms, out, None
            except Exception as exc:
                return None, None, str(exc)[:160]

        ms_xla, out_x, err_x = leg(fn_xla, args_xla)
        ms_pallas, out_p, err_p = leg(fn_pallas, args_pallas)
        case = {"kind": kind, "length": length,
                "xla_ms": round(ms_xla, 3) if ms_xla is not None else None,
                "pallas_ms": (round(ms_pallas, 3)
                              if ms_pallas is not None else None), **detail}
        if err_x:
            case["xla_error"] = err_x
        if err_p:
            case["pallas_error"] = err_p
        # Numerics gate on the REAL backend: both legs ran — compare.
        # bf16 flash reorders reductions, so the bar is loose (5% of the
        # output scale); an actual Mosaic miscompile is orders beyond it.
        mismatch = False
        if out_x is not None and out_p is not None:
            ox = np.asarray(out_x, dtype=np.float32)
            op = np.asarray(out_p, dtype=np.float32)
            denom = float(np.max(np.abs(ox))) or 1.0
            rel = float(np.max(np.abs(ox - op))) / denom
            case["rel_err"] = round(rel, 5)
            if not np.isfinite(rel) or rel > 0.05:
                mismatch = True
                case["numerics_mismatch"] = True
        results["cases"].append(case)
        print(json.dumps(case), flush=True)
        if beat is not None:
            beat()
        slot = wins.setdefault(kind, {}).setdefault(str(length), [])
        # Pallas wins only if it ran, MATCHED the XLA numerics, and beat
        # a working XLA leg; a broken XLA leg with working pallas also
        # counts (something must run).
        if ms_pallas is None or mismatch:
            slot.append(False)
        elif ms_xla is None:
            slot.append(True)
        else:
            slot.append(ms_pallas <= ms_xla)

    # prefill (one sequence per call, bucket-sized).  Every block below
    # checks want() BEFORE building its inputs: excluded kinds must not
    # pay device work (the whole point of --kinds is dodging a case
    # class that hangs).
    for s in lengths:
        if s % 128 or not want("prefill"):
            continue
        q = jax.random.normal(key, (1, s, nq, d), bf16)
        k = jax.random.normal(key, (1, s, nkv, d), bf16)
        v = jax.random.normal(key, (1, s, nkv, d), bf16)
        record("prefill", s, A.causal_attention, (q, k, v),
               PA.flash_causal_attention, (q, k, v), {})

    # decode + chunk + paged_decode across batch × cache length
    from ..ops.quant import quantize_kv_rows as _qkv
    for s in lengths:
        for b in batches:
            if not (want("decode") or want("decode_q8")):
                break
            q = jax.random.normal(key, (b, nq, d), bf16)
            kc = jax.random.normal(key, (b, s, nkv, d), bf16)
            vc = jax.random.normal(key, (b, s, nkv, d), bf16)
            pos = jnp.full((b,), s - 1, jnp.int32)     # worst-case frontier
            record("decode", s, A.decode_attention, (q, kc, vc, pos),
                   PA.flash_decode_attention, (q, kc, vc, pos),
                   {"batch": b})

            if want("decode_q8"):
                # int8 contiguous cache: XLA dequant vs in-VMEM kernel.
                kq, ksc = _qkv(kc)
                vq, vsc = _qkv(vc)
                ksc_c = ksc.astype(jnp.float32)
                vsc_c = vsc.astype(jnp.float32)
                record("decode_q8", s,
                       lambda *a: A.decode(a[0], a[1], a[2], a[5],
                                           impl="xla",
                                           k_scale=a[3], v_scale=a[4]),
                       (q, kq, vq, ksc_c, vsc_c, pos),
                       PA.flash_decode_attention_q8,
                       (q, kq, vq, ksc_c, vsc_c, pos), {"batch": b})

        if want("chunk") or want("chunk_q8"):
            # chunk prefill: one 128-token suffix against the window
            sc = min(128, s)
            q = jax.random.normal(key, (1, sc, nq, d), bf16)
            kc = jax.random.normal(key, (1, s, nkv, d), bf16)
            vc = jax.random.normal(key, (1, s, nkv, d), bf16)
            qpos = (jnp.arange(sc, dtype=jnp.int32) + (s - sc))[None]
            record("chunk", s, A.chunk_attention, (q, kc, vc, qpos),
                   PA.flash_chunk_attention, (q, kc, vc, qpos),
                   {"chunk": sc})

            if want("chunk_q8"):
                # int8-cache chunk: XLA dequant vs the in-VMEM q8 kernel.
                kq, ksc = _qkv(kc)
                vq, vsc = _qkv(vc)
                record("chunk_q8", s,
                       lambda *a: A.chunk(a[0], a[1], a[2], a[5],
                                          impl="xla",
                                          k_scale=a[3], v_scale=a[4]),
                       (q, kq, vq, ksc.astype(jnp.float32),
                        vsc.astype(jnp.float32), qpos),
                       PA.flash_chunk_attention_q8,
                       (q, kq, vq, ksc.astype(jnp.float32),
                        vsc.astype(jnp.float32), qpos), {"chunk": sc})

        # paged decode: pool sized for 8 slots of this length
        bs = 64
        for b in batches[1:]:
            if not (want("paged_decode") or want("paged_decode_q8")):
                break
            nb = b * (s // bs) + 1
            kp = jax.random.normal(key, (nkv, nb, bs, d), bf16)
            vp = jax.random.normal(key, (nkv, nb, bs, d), bf16)
            tables = jnp.asarray(
                np.arange(b * (s // bs), dtype=np.int32).reshape(b, s // bs))
            pos = jnp.full((b,), s - 1, jnp.int32)
            q = jax.random.normal(key, (b, nq, d), bf16)
            record("paged_decode", s, A.paged_decode,
                   (q, kp, vp, tables, pos),
                   PA.paged_decode_attention, (q, kp, vp, tables, pos),
                   {"batch": b})

            if want("paged_decode_q8"):
                # int8 pool variant: XLA half-byte gather+dequant vs the
                # in-VMEM dequant kernel.
                kq, ksc = _qkv(kp)
                vq, vsc = _qkv(vp)
                record("paged_decode_q8", s,
                       lambda *a: A.paged_decode(a[0], a[1], a[2], a[5],
                                                 a[6], impl="xla",
                                                 k_scale=a[3],
                                                 v_scale=a[4]),
                       (q, kq, vq, ksc, vsc, tables, pos),
                       PA.paged_decode_attention_q8,
                       (q, kq, vq, ksc, vsc, tables, pos), {"batch": b})

        # ragged paged decode: FULL tables + SKEWED per-slot lengths —
        # the mixed-length regime the ragged kernel exists for (the
        # dense paged kinds above measure at the uniform worst-case
        # frontier; measuring ragged there would hide exactly the
        # padded-window waste it removes).
        for b in batches[1:]:
            if not (want("ragged_decode") or want("ragged_decode_q8")):
                break
            nb = b * (s // bs) + 1
            kp = jax.random.normal(key, (nkv, nb, bs, d), bf16)
            vp = jax.random.normal(key, (nkv, nb, bs, d), bf16)
            tables = jnp.asarray(
                np.arange(b * (s // bs), dtype=np.int32).reshape(b, s // bs))
            # Slot i holds ~(i+1)/b of the full length: one long slot,
            # the rest progressively shorter.
            pos = jnp.asarray([max(0, s * (i + 1) // b - 1)
                               for i in range(b)], jnp.int32)
            q = jax.random.normal(key, (b, nq, d), bf16)
            if want("ragged_decode"):
                record("ragged_decode", s, A.ragged_decode,
                       (q, kp, vp, tables, pos),
                       RA.ragged_paged_decode_attention,
                       (q, kp, vp, tables, pos), {"batch": b})

            if want("ragged_decode_q8"):
                kq, ksc = _qkv(kp)
                vq, vsc = _qkv(vp)
                record("ragged_decode_q8", s,
                       lambda *a: A.ragged_decode(a[0], a[1], a[2], a[5],
                                                  a[6], impl="xla",
                                                  k_scale=a[3],
                                                  v_scale=a[4]),
                       (q, kq, vq, ksc, vsc, tables, pos),
                       RA.ragged_paged_decode_attention_q8,
                       (q, kq, vq, ksc, vsc, tables, pos), {"batch": b})

        # ragged speculative verify (ISSUE 15): the q_len=γ+1 extension
        # of the ragged decode case — same skewed per-slot lengths, a
        # γ+1 verify chunk per slot ending at the slot's frontier (the
        # chunk's own K/V already written, write-before-attend, so the
        # queries attend real content like a serving verify tick).
        for b in batches[1:]:
            if not (want("ragged_verify") or want("ragged_verify_q8")):
                break
            g = 5                                  # γ=4, the preset default
            nb = b * (s // bs) + 1
            kp = jax.random.normal(key, (nkv, nb, bs, d), bf16)
            vp = jax.random.normal(key, (nkv, nb, bs, d), bf16)
            tables = jnp.asarray(
                np.arange(b * (s // bs), dtype=np.int32).reshape(b, s // bs))
            # First-query positions: the slot's skewed frontier minus the
            # chunk (clamped non-negative) — verify masks per query row.
            pos = jnp.asarray([max(0, s * (i + 1) // b - g)
                               for i in range(b)], jnp.int32)
            q = jax.random.normal(key, (b, g, nq, d), bf16)
            if want("ragged_verify"):
                record("ragged_verify", s, A.ragged_verify,
                       (q, kp, vp, tables, pos),
                       RA.ragged_paged_verify_attention,
                       (q, kp, vp, tables, pos), {"batch": b, "g": g})

            if want("ragged_verify_q8"):
                kq, ksc = _qkv(kp)
                vq, vsc = _qkv(vp)
                record("ragged_verify_q8", s,
                       lambda *a: A.ragged_verify(a[0], a[1], a[2], a[5],
                                                  a[6], impl="xla",
                                                  k_scale=a[3],
                                                  v_scale=a[4]),
                       (q, kq, vq, ksc, vsc, tables, pos),
                       RA.ragged_paged_verify_attention_q8,
                       (q, kq, vq, ksc, vsc, tables, pos),
                       {"batch": b, "g": g})

        # paged chunk prefill (prefix-reuse admissions — engine/paged_kv.
        # chunk_prefill_paged): one 128-token suffix attending through a
        # slot's block table over a window of this length.
        if want("paged_chunk") and s >= 128 and s % bs == 0:
            sc = 128
            nb = s // bs
            kp = jax.random.normal(key, (nkv, nb + 1, bs, d), bf16)
            vp = jax.random.normal(key, (nkv, nb + 1, bs, d), bf16)
            table = jnp.arange(nb, dtype=jnp.int32)
            start = jnp.asarray([s - sc], jnp.int32)
            qpos = (jnp.arange(sc, dtype=jnp.int32) + (s - sc))[None]
            q = jax.random.normal(key, (1, sc, nq, d), bf16)
            record("paged_chunk", s,
                   lambda *a, s=s: A.paged_chunk(a[0], a[1], a[2], a[3],
                                                 a[4], a[5], s, impl="xla"),
                   (q, kp, vp, table, start, qpos),
                   lambda *a, s=s: PA.paged_chunk_attention(
                       a[0], a[1], a[2], a[3], a[4], s),
                   (q, kp, vp, table, start, qpos), {"chunk": sc})

    # Dispatch decision: pallas must win (or tie) at EVERY tested batch of
    # a (kind, length) to own it — robust beats optimal.  Each kind also
    # gets a "default" (the majority winner across its measured lengths,
    # ties to xla) so off-ladder shapes — e.g. the batched engine's
    # trimmed paged window — inherit a measured demotion instead of
    # silently staying on Pallas.
    dispatch = {}
    for kind, per in wins.items():
        owns = {length: all(v) for length, v in per.items()}
        table = {length: ("pallas" if won else "xla")
                 for length, won in owns.items()}
        table["default"] = ("pallas"
                            if sum(owns.values()) * 2 > len(owns) else "xla")
        dispatch[kind] = table
    results["dispatch"] = dispatch
    print(json.dumps({"dispatch": dispatch}), flush=True)
    if write_dispatch:
        publish_dispatch(results["backend"], results["model"], dispatch,
                         kernel_gen=PA.KERNEL_GEN)
    return results


def publish_dispatch(backend: str, model: str, dispatch: dict,
                     path: str = None, kernel_gen: int = None) -> bool:
    """Write the measured dispatch table, enforcing the artifact policy.

    A table measured on real hardware is a committed artifact; a CPU run
    must never clobber it (ops/attention.py would then ignore the file
    entirely and silently drop the TPU measurements), while
    a hardware run may always refresh, including replacing a stale cpu
    table.  A partial (--kinds / fast)
    run MERGES into a same-backend table — unmeasured kinds keep their
    prior winners — but a cross-backend refresh starts clean: mixing
    winners measured on different hardware would make the table
    meaningless.  Returns True if the table was written."""
    path = path or DISPATCH_PATH
    prior = {}
    try:
        with open(path) as f:
            prior = json.load(f)
    except (OSError, ValueError):
        pass
    prior_backend = prior.get("backend")
    if (prior_backend is not None and prior_backend != backend
            and backend == "cpu"):
        print(f"# REFUSING to overwrite {path}: it was measured on "
              f"{prior_backend!r}, this run is {backend!r} (delete the "
              "file to force)", flush=True)
        return False
    # Merge only into a same-backend, same-kernel-generation table:
    # winners measured on different hardware OR against older kernel
    # implementations must not mix with fresh ones.
    same_gen = (kernel_gen is None
                or prior.get("kernel_gen") == kernel_gen)
    merged = (dict(prior.get("dispatch") or {})
              if prior_backend == backend and same_gen else {})
    merged.update(dispatch)
    out = {"backend": backend, "model": model, "dispatch": merged}
    if kernel_gen is not None:
        out["kernel_gen"] = kernel_gen
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {path} ({len(dispatch)}/{len(merged)} kinds updated)",
          flush=True)
    return True


def measure(impl: str, tier_name: str, prompt_tokens: int, max_new: int,
            repeat: int) -> dict:
    os.environ["DLLM_ATTENTION"] = impl
    import dataclasses

    import jax

    from ..config import bench_cluster, tiny_cluster
    from ..engine.inference import InferenceEngine

    cluster = (tiny_cluster() if jax.default_backend() == "cpu"
               else bench_cluster())
    # Prefix reuse OFF: this harness measures the cold prefill kernels
    # (PrefixCache.take matches even a diverging entry's shared prefix, so
    # any repeat would otherwise prefill a ~1-bucket suffix, not the
    # prompt).  Belt and braces, the prompt HEAD varies per iteration too.
    tier = dataclasses.replace(getattr(cluster, tier_name),
                               enable_prefix_cache=False)
    engine = InferenceEngine(tier, seed=0)
    engine.warmup()

    filler = "user: " + ("benchmark the attention kernels now. " * 400)
    ttfts, tokps = [], []
    for i in range(repeat):
        # Head-varied per iteration, trimmed AFTER prepending so the total
        # stays at the requested token count under the ENGINE's tokenizer
        # (subword BPE since r3) and lands in the intended prefill bucket.
        tok = engine.tokenizer
        ids = tok.encode(f"variant {i} " + filler,
                         add_bos=False)[:prompt_tokens]
        res = engine.generate(tok.decode(ids), max_new_tokens=max_new)
        ttfts.append(res.ttft_ms)
        if res.tokens_per_s:
            tokps.append(res.tokens_per_s)
    del engine
    return {
        "impl": impl,
        "backend": jax.default_backend(),
        "tier": tier.name,
        "model": tier.model_preset,
        "prompt_tokens": prompt_tokens,
        "p50_ttft_ms": round(statistics.median(ttfts), 2),
        "p50_decode_tok_per_s": round(statistics.median(tokps), 1)
        if tokps else None,
        "repeat": repeat,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("mode", nargs="?", default="engine",
                    choices=("engine", "micro"))
    ap.add_argument("--tier", default="nano", choices=("nano", "orin"))
    ap.add_argument("--prompt-tokens", type=int, default=512)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--write-dispatch", action="store_true",
                    help="micro mode: publish bench/ab_dispatch.json")
    ap.add_argument("--fast", action="store_true",
                    help="micro mode: trimmed grid (headline shapes only)")
    ap.add_argument("--kinds", default=None,
                    help="micro mode: comma-separated kind subset to run "
                         "(isolate/exclude a case class)")
    ap.add_argument("--platform", default=None,
                    help="pin jax_platforms (e.g. cpu), like the "
                         "JAX_PLATFORMS environment variable")
    args = ap.parse_args(argv)

    from ..utils.compile_cache import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    if args.mode == "micro":
        micro_ab(args.tier, repeat=max(args.repeat, 10),
                 write_dispatch=args.write_dispatch, fast=args.fast,
                 kinds=(set(args.kinds.split(",")) if args.kinds else None))
        return

    results = {}
    prior = os.environ.get("DLLM_ATTENTION")
    try:
        for impl in ("xla", "pallas"):
            t0 = time.perf_counter()
            results[impl] = measure(impl, args.tier, args.prompt_tokens,
                                    args.max_new, args.repeat)
            results[impl]["wall_s"] = round(time.perf_counter() - t0, 1)
            print(json.dumps(results[impl]), flush=True)
    finally:
        # Don't leak the kill switch into the calling process (in-process
        # callers like the test suite share os.environ).
        if prior is None:
            os.environ.pop("DLLM_ATTENTION", None)
        else:
            os.environ["DLLM_ATTENTION"] = prior

    x, p = results["xla"], results["pallas"]
    verdict = {
        "ttft_ratio_pallas_over_xla": round(
            p["p50_ttft_ms"] / max(x["p50_ttft_ms"], 1e-9), 3),
        "decode_ratio_pallas_over_xla": round(
            (p["p50_decode_tok_per_s"] or 0)
            / max(x["p50_decode_tok_per_s"] or 1e-9, 1e-9), 3),
    }
    print(json.dumps({"verdict": verdict}))


if __name__ == "__main__":
    main()
