#!/usr/bin/env python3
"""The quickest proof that the serving main path still starts on the chip.

One process, one TPU chip by default:

    python chip_smoke.py            # device, serve, what ran, pool, kernels
    python chip_smoke.py --chips 4  # placement + tp=2 vs tp=1, nothing else
    python chip_smoke.py --config kimi-linear-48b-a3b  # that cell's pool

Phases print their own lines; any failure exits non-zero at once.  The
last line of stdout is one JSON object naming the device as jax reports
it.  There is no CPU branch: off-TPU the script fails in the device
phase without serving anything (tests/test_chip_smoke.py drives the
phase FUNCTIONS on the CPU test mesh as the rehearsal).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

# Kernel-comparison tolerances, fixed here before any run.  float32 is
# the pin tests/test_pallas_attention.py uses (kernel and reference both
# at full matmul precision); bfloat16 —
# the dtype the tiers serve — is compared against the float32 reference
# of the same bf16-valued inputs, bounded by the dtype: the kernel rounds
# the softmax weights and the output to 8 mantissa bits (2^-8 = 3.9e-3
# relative each).
KERNEL_TOL = {"float32": 2e-5, "bfloat16": 2e-2,
              # Two chained products over thousands of terms, the one
              # between them rounded to the dtype; another summation order.
              ("float32", "grouped_product"): 2e-4,
              ("bfloat16", "grouped_product"): 5e-2,
              # The up-projected keys and values and the probabilities
              # rounded to the dtype between three chained products.
              ("bfloat16", "latent_chunk"): 5e-2}

# Mamba-1's chunk scan as both row cells of the benchmark run it:
# positions, states, channels.
SCAN_SHAPE = (256, 16, 5120)
# The latent chunk attention as ``sarvam-105b``'s lane runs it at a rung
# three quarters written: heads, queries, window rows, the chunk's end,
# latent numbers, the row's resting width (a head's keys and values 128
# each, 64 rotary numbers).
LATENT_SHAPE = (64, 256, 4096, 3072, 512, 640)

LONG_SENTENCE = ("Rivers carry sediment from the mountains to the delta, "
                 "where the channels split, slow down and drop their load. ")


class SmokeFailure(AssertionError):
    """A phase found something wrong; the smoke exits non-zero."""


def say(phase: str, msg: str) -> None:
    print(f"[smoke:{phase}] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# =============================================================================
# device
# =============================================================================

def phase_device(chips: int) -> Dict[str, Any]:
    """jax.devices() must be TPUs — ``chips`` of them at least.  No
    platform is configured here and nothing falls back."""
    import jax
    import jaxlib
    devices = jax.devices()
    d0 = devices[0]
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                     # not pip-installed: say so
        libtpu = "unknown"
    say("device", f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
                  f"libtpu {libtpu}")
    say("device", f"platform={d0.platform} device_kind={d0.device_kind!r} "
                  f"count={len(devices)}")
    check(d0.platform == "tpu",
          f"jax found no TPU (platform {d0.platform!r}): the smoke only "
          f"runs on the chip")
    check(len(devices) >= chips,
          f"needs {chips} chip(s), jax reports {len(devices)}")
    from distributed_llm_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    say("device", f"compile cache: {enable_persistent_compile_cache()} "
                  f"(JAX_COMPILATION_CACHE_DIR "
                  f"{'set' if from_env else 'unset'})")
    from distributed_llm_tpu import native
    say("device", "host hot loops (tokenizer merges, routing features): "
                  + ("native library" if native.available()
                     else "Python fallback"))
    for var in ("DLLM_ATTENTION", "DLLM_RAGGED"):
        check(os.environ.get(var) is None,
              f"{var} is set: the smoke checks the default path")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# =============================================================================
# serve
# =============================================================================

def smoke_cluster():
    """What ``default_cluster()`` serves on an accelerator, with the
    nano tier at the north star's full size: nano_1b (bf16, full width
    and depth, 8192 context) beside orin_bench int8 — both on one 16 GB
    chip.  Weights come from the seed; no checkpoint is read."""
    from distributed_llm_tpu.config import flagship_cluster
    from distributed_llm_tpu.serving.router import default_cluster
    base = default_cluster()
    return dataclasses.replace(
        base,
        nano=dataclasses.replace(flagship_cluster(n_devices=1).nano,
                                 checkpoint_path=None),
        orin=dataclasses.replace(base.orin, checkpoint_path=None))


class Served(NamedTuple):
    router: Any
    client: Any
    record: Dict[str, Any]


def _engine(router, tier: str):
    return router.tiers[tier].server_manager.engine()


def _compiled_keys(router) -> Dict[str, Dict[str, frozenset]]:
    """Per tier, per stage: the programs the engine has minted
    (``_note_compile``)."""
    return {name: {stage: frozenset(keys) for stage, keys
                   in sorted(_engine(router, name)._compiled.items())}
            for name in router.tiers}


def _compile_counts(router) -> Dict[str, Dict[str, int]]:
    return {name: {stage: len(keys) for stage, keys in stages.items()}
            for name, stages in _compiled_keys(router).items()}


def _manager_devices(manager) -> set:
    from distributed_llm_tpu.engine.manager import mesh_devs
    return set(mesh_devs(manager.mesh) or manager.devices)


def _tier_devices(router, tier: str) -> set:
    return _manager_devices(router.tiers[tier].server_manager)


def _check_placement(manager, name: str) -> int:
    """Every array the engine holds lives on the devices its manager was
    carved, and nowhere else; returns the bytes held."""
    import jax
    engine, want = manager.engine(), _manager_devices(manager)
    total = 0
    for label in ("params", "pool", "params_d", "pool_d"):
        for leaf in jax.tree_util.tree_leaves(getattr(engine, label, None)):
            check(leaf.devices() == want,
                  f"{name}: a {label} array lives on "
                  f"{sorted(d.id for d in leaf.devices())}, the tier owns "
                  f"{sorted(d.id for d in want)}")
            total += leaf.nbytes
    return total


def _chat(client, record: Dict[str, Any], router, *, label: str,
          message: str, strategy: str, session: str,
          expect_device: Optional[str], expect_cache_hit: bool = False
          ) -> Dict[str, Any]:
    """One POST /chat with the checks every request gets."""
    t0 = time.perf_counter()
    resp = client.post("/chat", json={"message": message,
                                      "strategy": strategy,
                                      "session_id": session})
    wall_ms = (time.perf_counter() - t0) * 1000.0
    check(resp.status_code == 200, f"{label}: HTTP {resp.status_code}")
    body = resp.get_json()
    reply = body.get("reply") or ""
    check(bool(reply.strip()), f"{label}: empty reply")
    check(not reply.startswith(("Request failed", "System Error",
                                "No response available")),
          f"{label}: error-shaped reply {reply[:120]!r}")
    check(body.get("tokens", 0) > 0, f"{label}: tokens={body.get('tokens')}")
    if expect_device is not None:
        check(body.get("device") == expect_device,
              f"{label}: served by {body.get('device')!r}, expected "
              f"{expect_device!r} ({body.get('reasoning')})")
    check(bool(body.get("cache_hit")) == expect_cache_hit,
          f"{label}: cache_hit={body.get('cache_hit')}, expected "
          f"{expect_cache_hit}")
    row = {"label": label, "device": body["device"],
           "method": body.get("method"), "cache_hit": body["cache_hit"],
           "wall_ms": round(wall_ms, 1)}
    if not expect_cache_hit:
        res = router.tiers[body["device"]].last_result
        check(res is not None and res.gen_tokens > 0,
              f"{label}: the engine generated no token")
        row.update(prompt_tokens=res.prompt_tokens,
                   gen_tokens=res.gen_tokens,
                   ttft_ms=round(res.ttft_ms, 1),
                   total_ms=round(res.total_ms, 1))
    record["requests"].append(row)
    say("serve", " ".join(f"{k}={v}" for k, v in row.items()))
    return body


def _bring_up(phase: str, cluster, devices: Optional[Sequence[Any]]
              ) -> Served:
    """Router + create_app + its test client on ``cluster``, every tier
    started and placed.  Warm-up is set-up, timed apart from the
    requests: start_server builds the engine and compiles its program
    family (the lazy start inside a first request would bill minutes of
    compile to it, past ``request_timeout_s``)."""
    from distributed_llm_tpu.config import describe_cluster
    from distributed_llm_tpu.obs import Observability
    from distributed_llm_tpu.serving.app import BASE_CONFIG, create_app
    from distributed_llm_tpu.serving.router import Router

    say(phase, "cluster: " + describe_cluster(cluster))
    # A fresh observability bundle: the counters read later are this
    # router's alone.
    router = Router(strategy="token", config=dict(BASE_CONFIG),
                    cluster=cluster, devices=devices,
                    observability=Observability(slow_ms=None))
    served = Served(router, create_app(router=router).test_client(),
                    {"requests": [], "warmup_s": {}})
    try:
        for name, tier in router.tiers.items():
            t0 = time.perf_counter()
            tier.server_manager.start_server()
            served.record["warmup_s"][name] = round(
                time.perf_counter() - t0, 1)
            held = _check_placement(tier.server_manager, name)
            say(phase, f"tier {name} up: warm-up (build + compile) "
                       f"{served.record['warmup_s'][name]} s; holds "
                       f"{held / 2**30:.2f} GiB on device(s) "
                       f"{sorted(d.id for d in _tier_devices(router, name))}")
    except BaseException:
        router.drain(timeout_s=5.0)       # leave no engine running
        raise
    return served


def phase_serve(cluster, devices: Optional[Sequence[Any]] = None) -> Served:
    """The request mix through /chat and /chat/stream, then /stats
    /metrics /health.  Leaves the router up (``phase_what_ran`` reads
    the live engines); ``phase_drain`` ends it."""
    served = _bring_up("serve", cluster, devices)
    try:
        _serve(served)
    except BaseException:
        served.router.drain(timeout_s=5.0)
        raise
    return served


def _serve(served: Served) -> None:
    import jax

    from distributed_llm_tpu.engine.inference import pick_bucket
    router, client, record = served
    cluster_tier = {t.name: t for t in router.cluster.tiers()}
    record["compiled_after_warmup"] = _compile_counts(router)

    backend_compiles: List[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: backend_compiles.append(
            str(kw.get("fun_name")))
        if event == "/jax/core/compile/backend_compile_duration" else None)

    complex_a = ("Compare quicksort and mergesort: why is one faster? "
                 "Explain the trade-offs in detail.")
    complex_b = ("Compare heapsort and insertion sort: why is one slower? "
                 "Explain the trade-offs in detail.")
    long_msg = LONG_SENTENCE * 40         # > the token strategy's threshold

    def same_shape_pair(tier: str, strategy: str, first: str, second: str,
                        sessions: Sequence[str]) -> None:
        """Two requests of one prompt bucket: whatever the first still
        had to compile, the second compiles nothing — unless it decodes
        further than anything before it (see below)."""
        _chat(client, record, router, label=f"{tier}#1", message=first,
              strategy=strategy, session=sessions[0], expect_device=tier)
        before, n_xla = _compiled_keys(router), len(backend_compiles)
        _chat(client, record, router, label=f"{tier}#2", message=second,
              strategy=strategy, session=sessions[1], expect_device=tier)
        a, b = record["requests"][-2:]
        buckets = [pick_bucket(cluster_tier[tier].prefill_buckets,
                               r["prompt_tokens"],
                               cluster_tier[tier].model().max_seq_len)
                   for r in (a, b)]
        check(buckets[0] == buckets[1],
              f"{tier}: the pair landed in prompt buckets {buckets}")
        after = _compiled_keys(router)
        # The one program family that grows with the CONVERSATION, not
        # the prompt: the dense windowed tick mints a decode program per
        # window rung, lazily, the first time a slot's position crosses
        # it.  A rung deeper than any seen before is a new shape, not a
        # recompile; the ragged tick has one decode program for life.
        new_rungs = (after[tier].get("decode", frozenset())
                     - before[tier].get("decode", frozenset()))
        if new_rungs:
            check(not _engine(router, tier).ragged and all(
                key > old for key in new_rungs
                for old in before[tier]["decode"]),
                f"{tier}: decode programs {sorted(new_rungs)} compiled "
                f"beside {sorted(before[tier]['decode'])}")
            say("serve", f"{tier}#2 decoded past the warmed window: the "
                         f"dense tick compiled rung(s) {sorted(new_rungs)} "
                         f"mid-request")
        after[tier]["decode"] = before[tier].get("decode", frozenset())
        check(after == before,
              f"a program was compiled on the second {tier} request: "
              f"{before} -> {_compiled_keys(router)}")
        check(len(backend_compiles) - n_xla <= len(new_rungs),
              f"XLA compiled on the second {tier} request: "
              f"{backend_compiles[n_xla:]}")

    same_shape_pair("nano", "token", "What is the capital of France?",
                    "Name three primary colours.", ("n1", "n2"))
    same_shape_pair("orin", "heuristic", complex_a, complex_b, ("o1", "o2"))

    # Long prompt: the token strategy sends it to orin, where it is a
    # cold CHUNKED prefill (bucket > prefill_chunk_tokens).
    _chat(client, record, router, label="orin#3-long", message=long_msg,
          strategy="token", session="o3", expect_device="orin")

    # Response cache: same strategy + same text, another session.
    _chat(client, record, router, label="nano#1-repeat",
          message="What is the capital of France?", strategy="token",
          session="n3", expect_device="nano", expect_cache_hit=True)

    # Multi-turn follow-up: the prompt extends session n1's parked
    # prefix, so only the suffix is prefilled (chunk program).
    hits_before = _engine(router, "nano").prefix_cache.stats()["hits"]
    _chat(client, record, router, label="nano#1-followup",
          message="And what is its population?", strategy="token",
          session="n1", expect_device="nano")
    hits = _engine(router, "nano").prefix_cache.stats()["hits"]
    check(hits == hits_before + 1,
          f"the follow-up did not reuse the parked prefix "
          f"(prefix hits {hits_before} -> {hits})")

    # One stream, consumed to the end.
    t0 = time.perf_counter()
    resp = client.post("/chat/stream", json={
        "message": "Tell me about rivers.", "strategy": "token",
        "session_id": "n4"})
    check(resp.status_code == 200, f"stream: HTTP {resp.status_code}")
    events = [json.loads(frame[len("data: "):])
              for frame in resp.text.strip().split("\n\n")
              if frame.startswith("data: ")]
    check(bool(events) and events[0].get("meta") is True,
          "stream: no meta event")
    check(events[-1].get("done") is True,
          f"stream: did not end with done: {events[-1]}")
    check(not any("error" in e for e in events),
          f"stream: error event {[e for e in events if 'error' in e]}")
    text = "".join(e.get("delta", "") for e in events)
    check(bool(text.strip()) and events[-1].get("tokens", 0) > 0,
          "stream: no tokens")
    check(events[0].get("device") == "nano",
          f"stream: served by {events[0].get('device')!r}")
    row = {"label": "nano#stream", "device": events[0]["device"],
           "deltas": sum(1 for e in events if "delta" in e),
           "gen_tokens": events[-1]["tokens"],
           "ttft_ms": events[-1].get("ttft_ms"),
           "total_ms": events[-1].get("total_ms"),
           "wall_ms": round((time.perf_counter() - t0) * 1000.0, 1)}
    record["requests"].append(row)
    say("serve", " ".join(f"{k}={v}" for k, v in row.items()))

    # /stats, /metrics, /health: nothing failed over, retried or opened.
    stats = client.get("/stats").get_json()
    record["stats"] = stats
    for name in router.tiers:
        entry = stats["tiers"][name]
        check(entry.get("ok") is True, f"/stats: tier {name} not ok")
        check({"tokenize", "prefill", "decode"}
              <= set(entry.get("phases", {})),
              f"/stats: tier {name} phases {sorted(entry.get('phases', {}))}")
        br = stats["breaker"][name]
        check(br["state"] == "closed" and br["opened_total"] == 0,
              f"/stats: breaker {name} {br}")
    check(stats["degraded_served"] == 0, "/stats: degraded responses served")
    check(stats["slo"]["violations"]["error"] == 0,
          f"/stats: SLO error violations {stats['slo']['violations']}")
    metrics = client.get("/metrics").text
    for family in ("dllm_failovers_total", "dllm_retries_total"):
        fired = [ln for ln in metrics.splitlines()
                 if ln.startswith(family) and float(ln.rsplit(" ", 1)[1]) > 0]
        check(not fired, f"/metrics: {fired}")
    health = client.get("/health")
    check(health.status_code == 200
          and health.get_json()["status"] == "ok",
          f"/health: {health.status_code}")
    for name, tier in router.tiers.items():
        _check_placement(tier.server_manager, name)
    record["compiled_after_requests"] = _compile_counts(router)
    record["backend_compiles_total"] = len(backend_compiles)
    say("serve", f"/stats /metrics /health ok: no failover, retry or "
                 f"breaker event; SLO violations "
                 f"{stats['slo']['violations']}")


def phase_what_ran(served: Served) -> Dict[str, Any]:
    """The record of which path each tier actually took."""
    from distributed_llm_tpu.ops.pallas_attention import kernel_mode
    out: Dict[str, Any] = {}
    say("what-ran", f"pallas kernels: {kernel_mode()}")
    for name in served.router.tiers:
        engine = _engine(served.router, name)
        span = engine.paged.blocks_per_slot * engine.paged.block_size
        row = {
            "engine": type(engine).__name__,
            "attention_impl": engine.cfg.attention_impl,
            "tick": "ragged fused" if engine.ragged else "dense windowed",
            "decode_attention": engine.decode_attention_form(),
            # Of the decode ticks so far, the share launched with
            # everything already on the device (no upload in prepare).
            "tick_resident_share": engine.tick_stats()["resident_share"],
            "speculation": bool(engine.spec),
            "span": span,
            "compiled_after_warmup":
                served.record["compiled_after_warmup"][name],
            "compiled_after_requests":
                served.record["compiled_after_requests"][name],
            "warmup_s": served.record["warmup_s"][name],
        }
        out[name] = row
        say("what-ran", f"tier {name}: " + json.dumps(row, sort_keys=True))
    import jax
    for d in sorted({d for name in served.router.tiers
                     for d in _tier_devices(served.router, name)},
                    key=lambda d: d.id):
        mem = d.memory_stats() or {}
        say("what-ran", f"device {d.id}: peak_bytes_in_use="
                        f"{mem.get('peak_bytes_in_use', 'not reported')} "
                        f"bytes_in_use="
                        f"{mem.get('bytes_in_use', 'not reported')} "
                        f"bytes_limit="
                        f"{mem.get('bytes_limit', 'not reported')}")
    say("what-ran", f"XLA backend compiles since warm-up: "
                    f"{served.record['backend_compiles_total']}")
    return out


# =============================================================================
# the pool's programs
# =============================================================================
def pool_programs(engine, pool, windows: Sequence[int],
                  chunks: Sequence[Tuple[int, int]] = (), cow: bool = False
                  ) -> Dict[str, Tuple[Any, int]]:
    """The engine's OWN pool programs, compiled against ``pool`` where it
    lives — the engine's pool on the attached chip, or the shapes of a
    pool on a described one (``jax.eval_shape(init_pool)``, as
    tests/test_tpu_compile.py builds it beside a tiny engine): the decode
    tick at each table window of ``windows`` (tokens; the ragged tick
    takes the full span), the chunk-prefill program per (chunk, window)
    of ``chunks``, ``copy_block``.  Gives ``{label: (compiled, position
    of the pool argument)}``.  Nothing runs."""
    bs = engine.paged.block_size
    mb = engine.paged.blocks_per_slot
    out = {}
    for w in windows:
        wb = mb if engine.ragged else w // bs
        out[f"decode tick, window {wb * bs}"] = engine.compile_pool_program(
            "decode", wb, pool), 1
    for c, w in chunks:
        out[f"chunk prefill ({c}, {w})"] = engine.compile_pool_program(
            "chunk_prefill", (c, w), pool), 1
    if cow:
        out["copy_block"] = engine.compile_pool_program(
            "copy_block", pool=pool), 0
    return out


def pool_program_facts(compiled, pool_arg: int, pool) -> Dict[str, Any]:
    """What says a compiled pool program leaves the pool in place: its
    temporaries, the aliased bytes beside everything it returns, whether
    the pool's input and output formats agree, the layout each array
    comes in with, and the program's ``pool_sized_moves``
    (``obs/program_scopes.py``: the count GET /debug/programs gives)."""
    from distributed_llm_tpu.obs.program_scopes import pool_sized_moves
    fmt_in = compiled.input_formats[0][pool_arg]
    fmt_out = compiled.output_formats
    if not isinstance(fmt_out, dict):
        fmt_out = fmt_out[-1]
    mem = compiled.memory_analysis()
    return {"temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "formats_match": fmt_in == fmt_out,
            "major_to_minor": {key: list(fmt.layout.major_to_minor)
                               for key, fmt in fmt_in.items()},
            "pool_sized_moves": pool_sized_moves(compiled.as_text(), pool)}


def phase_pool_programs(served, tiers: Optional[Sequence[str]] = None
                        ) -> Dict[str, Any]:
    """Per tier (of ``tiers``; every tier of the router by default): the
    pool's format at rest, and for the decode tick and one chunk program
    what the chip's compiler made of them — to be set beside the compile
    for a described v5e of tests/test_tpu_compile.py.  On the chip a
    pool-sized array that does not rest row-major
    (``program_scopes.POOL_SIZED_BYTES``; DESIGN.md "A pool array has one
    format"), and a pool program that does not alias the pool, gives it
    back in another format than it took it, or moves it (a pool-sized
    ``copy``, a stacked ``ys``) fails the smoke."""
    from distributed_llm_tpu.obs.program_scopes import pool_sized
    out: Dict[str, Any] = {}
    for name in tiers or served.router.tiers:
        engine = _engine(served.router, name)
        at_rest = engine.pool_stats()["formats"]
        say("pool", f"tier {name}: pool at rest " + json.dumps(at_rest))
        for key, x in engine.pool.items():
            check(at_rest[key]["row_major"] or not pool_sized(x),
                  f"{name}: pool[{key!r}] {at_rest[key]['shape']} rests "
                  f"{at_rest[key]['major_to_minor']}, not row-major")
        bs = engine.paged.block_size
        chunk = engine._reuse_buckets[0]
        programs = pool_programs(
            engine, engine.pool, [engine._buckets[0]],
            [(chunk, max(engine._chunk_windows[0], -(-chunk // bs) * bs))],
            cow=not engine.cfg.hybrid)
        out[name] = {"at_rest": at_rest}
        for label, (compiled, pool_arg) in programs.items():
            facts = pool_program_facts(compiled, pool_arg, engine.pool)
            out[name][label] = facts
            say("pool", f"tier {name}: {label}: " + json.dumps(facts))
            check(facts["formats_match"],
                  f"{name}: {label} returns the pool in another format")
            if engine._pool_donated:
                check(facts["output_bytes"] - facts["alias_bytes"] < 1 << 20,
                      f"{name}: {label} does not alias the pool: {facts}")
                check(not facts["pool_sized_moves"],
                      f"{name}: {label} moves the pool: {facts}")
    return out


def benchmark_cluster(config: str):
    """``benchmark/cluster.py``, loaded by its path, and the configuration
    ``benchmark/configs/<config>.json`` as it reads one:
    tests/test_tpu_compile.py takes a tier's settings from the pair,
    ``bench_served`` the built tiers."""
    import importlib.util
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "benchmark")
    spec = importlib.util.spec_from_file_location(
        "_benchmark_cluster", os.path.join(bench, "cluster.py"))
    cluster = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cluster)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        return cluster, json.load(f)


def bench_served(config: str, seed: int = 0):
    """A benchmark configuration's tiers, built and warmed the way
    ``benchmark/run.py`` builds them (``benchmark/cluster.py`` ``build``):
    weights from the seed at the cell's real sizes, on the first device."""
    import jax
    cluster, entries = benchmark_cluster(config)
    return cluster.build(entries, seed, False, jax.devices()[:1])


def phase_drain(served: Served) -> None:
    """router.drain(): the edge answers 503 and every engine stops."""
    out = served.router.drain()
    check(all("error" not in v for v in out.values()), f"drain: {out}")
    check(served.client.get("/health").status_code == 503,
          "drain: /health did not flip to 503")
    resp = served.client.post("/chat", json={"message": "late"})
    check(resp.status_code == 503, f"drain: /chat {resp.status_code}")
    say("serve", f"drained: {out}")


# =============================================================================
# kernels
# =============================================================================

class KernelCase(NamedTuple):
    kind: str                      # what it serves (tolerance key)
    pallas: Callable               # the Pallas entry
    xla: Callable                  # its XLA reference (same arguments)
    make_args: Callable            # () -> tuple of arrays, from a seed


def kernel_cases(nq: int, nkv: int, d: int, dtype, *, batch: int = 8,
                 block: int = 64, blocks_per_slot: int = 32,
                 prefill_len: int = 1024,
                 grouped: Optional[Dict[str, tuple]] = None,
                 scan: tuple = SCAN_SHAPE,
                 latent: tuple = LATENT_SHAPE
                 ) -> Dict[str, KernelCase]:
    """The main path's Pallas kernels at one head geometry, each with
    its XLA reference and a seeded argument builder.  chip_smoke runs
    them on the chip; tests/test_tpu_compile.py compiles the same
    entries for a described chip from ``jax.eval_shape(make_args)``.
    ``grouped``: the routed experts' grouped product (no heads in it) as
    {cell: (rows, groups a layer, in, out)}; by default the decode ticks
    of the benchmark's two routed cells at the widths they store.
    ``scan``: Mamba-1's recurrence over a chunk (no heads in it either,
    float32 whatever ``dtype``) as (positions, state, inner); by default
    a chunk of the shared-K/V family's benchmark cell.  ``latent``: the
    latent chunk attention (its own heads) as ``LATENT_SHAPE`` reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_tpu.ops import attention as A
    from distributed_llm_tpu.ops import grouped_product as GP
    from distributed_llm_tpu.ops import latent_chunk_attention as LCA
    from distributed_llm_tpu.ops import pallas_attention as PA
    from distributed_llm_tpu.ops import rows_attention as RW
    from distributed_llm_tpu.ops import ssm_chunk_scan as SC

    span = block * blocks_per_slot
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def rand(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    def rows_pool():
        """q + shuffled non-contiguous block tables + skewed per-slot
        positions (one slot near each end of the span) over a WHOLE
        token-major pool of two layers, a K/V head to every query head
        (what the served MHA tick hands ``ops/rows_attention.py``); the
        first slot idle over the trash block."""
        nb = batch * blocks_per_slot + 1                  # + trash block 0
        perm = np.random.default_rng(0).permutation(nb - 1) + 1
        tables = perm.reshape(batch, blocks_per_slot).astype(np.int32)
        pos = np.linspace(5, span - 2, batch).astype(np.int32)
        return (rand(keys[0], (batch, nq, d)),
                rand(keys[1], (2, nb, block, nq * d)),
                rand(keys[2], (2, nb, block, nq * d)),
                jnp.asarray(tables).at[0].set(0),
                jnp.asarray(pos).at[0].set(0))

    def experts(rows, per_layer, k, n):
        """Rows over a third of the MIDDLE layer's groups of a stack of
        three, a third of the rows in no group; an up and a down
        matrix a group."""
        sizes = np.zeros(3 * per_layer, np.int32)
        live = per_layer + np.arange(0, per_layer, 3)
        np.add.at(sizes, np.random.default_rng(0).choice(
            live, rows - rows // 3), 1)
        return (rand(keys[0], (rows, k)),
                rand(keys[1], (3 * per_layer, k, n)) * k ** -0.5,
                rand(keys[2], (3 * per_layer, n, k)) * n ** -0.5,
                jnp.asarray(sizes))

    def up_and_down(product):
        """Both products of an expert: [in, out] then [out, in]; only
        rows in a group compare (``ragged_dot`` defines no others)."""
        def run(x, up, down, sizes):
            a = product(x, up, sizes)
            y = product(jax.nn.relu(a).astype(x.dtype), down, sizes)
            in_group = jnp.arange(x.shape[0]) < jnp.sum(sizes)
            return jnp.where(in_group[:, None], y, 0)
        return run

    def ragged_ffn(x, gate, up, down, sizes):
        u = jax.lax.ragged_dot(x, up, sizes)
        h = (GP.activation(u) if gate is None else
             GP.activation(jax.lax.ragged_dot(x, gate, sizes), u))
        return jax.lax.ragged_dot(h, down, sizes)

    def ffn(impl):
        """An expert layer's whole FFN ([in, out], activation, [out, in]);
        only rows in a group compare."""
        def run(x, up, down, sizes, gate=None):
            y = impl(x, gate, up, down, sizes)
            in_group = jnp.arange(x.shape[0]) < jnp.sum(sizes)
            return jnp.where(in_group[:, None], y, 0)
        return run

    def gated_experts(*shape):
        x, up, down, sizes = experts(*shape)
        gate = rand(keys[3], up.shape) * up.shape[1] ** -0.5
        return x, up, down, sizes, gate

    def scan_args():
        """Time steps in the published range, the last eighth padding
        (0), decays A = -1..-state a channel."""
        t, n, c = scan
        f32 = jnp.float32
        dt = jnp.exp(jax.random.uniform(keys[0], (t, c), f32, -6.9, -2.3))
        dt = dt.at[t - t // 8:].set(0.0)
        return (dt, jax.random.normal(keys[1], (t, c), f32),
                jax.random.normal(keys[2], (t, n), f32),
                jax.random.normal(keys[3], (t, n), f32),
                -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=f32)[:, None],
                                  (n, c)),
                jax.random.normal(jax.random.fold_in(keys[0], 1), (n, c),
                                  f32))

    def scan_reference(dt, u, b, c, a, state):
        def step(state, x):
            dt_t, u_t, b_t, c_t = x
            state = (jnp.exp(dt_t[None, :] * a) * state
                     + (dt_t * u_t)[None, :] * b_t[:, None])
            return state, jnp.sum(state * c_t[:, None], axis=0)
        state, y = jax.lax.scan(step, state, (dt, u, b, c))
        return jnp.concatenate([y, state])

    l_heads, l_s, l_w, l_end, l_dc, l_row = latent
    l_dn, l_dr, l_scale = 128, 64, 192 ** -0.5

    def latent_args():
        return (rand(keys[0], (1, l_s, l_heads, l_dn)),
                rand(keys[1], (1, l_s, l_heads, l_dr)),
                rand(keys[2], (1, l_w, l_row)),
                rand(keys[3], (l_dc, l_heads, 2 * l_dn)) * l_dc ** -0.5,
                (l_end - l_s + jnp.arange(l_s, dtype=jnp.int32))[None])

    if grouped is None:
        grouped = {"wide-reasoning": (96, 16, 2688, 1920),
                   "reasoned-reply": (32, 16, 3584, 1024)}
    grouped_cases = {
        f"grouped_product.{cell}": KernelCase(
            "grouped_product", up_and_down(GP.grouped_product),
            up_and_down(jax.lax.ragged_dot),
            lambda shape=shape: experts(*shape))
        for cell, shape in grouped.items()}
    # An expert layer's whole FFN as ONE call (PR 53) against the chain of
    # ``ragged_dot`` calls: no gate where the cell's experts have none.
    for cell, shape in grouped.items():
        gated = cell != "wide-reasoning"
        grouped_cases[f"grouped_ffn.{cell}"] = KernelCase(
            "grouped_product", ffn(GP.grouped_ffn), ffn(ragged_ffn),
            lambda shape=shape, make=gated_experts if gated else experts:
            make(*shape))

    return {
        "flash_causal_attention": KernelCase(
            "prefill", PA.flash_causal_attention, A.causal_attention,
            lambda: tuple(rand(key, (1, prefill_len, n, d))
                          for key, n in zip(keys, (nq, nkv, nkv)))),
        # The served tick's own kernel (no head-major view: the pool
        # whole, a traced layer), against the XLA form it replaces.
        "paged_rows_decode_attention": KernelCase(
            "paged_decode",
            lambda *a: RW.paged_rows_decode_attention(*a, jnp.int32(1)),
            lambda *a: A.paged_decode(*a, impl="xla", layer=jnp.int32(1)),
            rows_pool),
        **grouped_cases,
        "ssm_chunk_scan": KernelCase(
            "ssm_scan",
            lambda *a: jnp.concatenate(SC.ssm_chunk_scan(*a)),
            scan_reference, scan_args),
        # The latent row's chunk attention by blocks of the window (PR
        # 61), against the plain form (on float32 arguments: nothing
        # rounded).
        "latent_chunk_attention": KernelCase(
            "latent_chunk",
            lambda *a: LCA.latent_chunk_attention(*a, scale=l_scale),
            lambda *a: LCA.plain(*a, scale=l_scale), latent_args),
    }


def compare_kernels(cases: Dict[str, KernelCase], dtype_name: str
                    ) -> Dict[str, float]:
    """Run every case's Pallas entry and its XLA reference on the same
    device arrays and hold the kernel to ``KERNEL_TOL``.  The reference
    is always float32 at full matmul precision; the kernel runs as
    served, except that float32 inputs get full matmul precision too
    (the tests' pin is a float32 pin)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    errs: Dict[str, float] = {}
    for name, case in cases.items():
        args = case.make_args()
        with jax.default_matmul_precision("highest"):
            want = jax.jit(case.xla)(*[
                a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a
                for a in args])
        with jax.default_matmul_precision(
                "highest" if dtype_name == "float32" else "default"):
            got = jax.jit(case.pallas)(*args)
        got = np.asarray(jax.block_until_ready(got), np.float32)
        want = np.asarray(want, np.float32)
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"kernel {name} [{dtype_name}]: shape {got.shape} vs "
              f"{want.shape}, finite={np.isfinite(got).all()}")
        tol = KERNEL_TOL.get((dtype_name, case.kind), KERNEL_TOL[dtype_name])
        err = float(np.max(np.abs(got - want)))
        errs[name] = err
        ok = np.allclose(got, want, atol=tol, rtol=tol)
        say("kernels", f"{name} ({case.kind}) [{dtype_name}] max|err|="
                       f"{err:.3e} tol={tol:g} {'ok' if ok else 'MISMATCH'}")
        check(ok, f"kernel {name} [{dtype_name}] differs from its XLA "
                  f"reference: max|err|={err:.3e} > {tol:g}")
    return errs


def phase_kernels() -> None:
    """The Pallas entries against their XLA references at nano_1b widths,
    compiled — a kernel that fails here fails the smoke; it is not
    demoted."""
    import jax.numpy as jnp

    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.ops import ssm_chunk_scan
    from distributed_llm_tpu.ops.pallas_attention import kernel_mode
    check(kernel_mode() == "compiled",
          f"the Pallas kernels are in {kernel_mode()} mode")
    cfg = MODEL_PRESETS["nano_1b"]
    say("kernels", f"nano_1b widths: Nq={cfg.num_heads} "
                   f"Nkv={cfg.num_kv_heads} D={cfg.head_dim}; kernels "
                   f"compiled (not interpreted)")
    say("kernels", f"ssm_chunk_scan at {SCAN_SHAPE} (positions, states, "
                   f"channels): serves={ssm_chunk_scan.serves(*SCAN_SHAPE)}"
                   f", {ssm_chunk_scan.lane_widths(SCAN_SHAPE[2])} "
                   f"lane-width(s) of channels a grid step")
    for dtype in (jnp.float32, jnp.bfloat16):
        compare_kernels(kernel_cases(cfg.num_heads, cfg.num_kv_heads,
                                     cfg.head_dim, dtype),
                        jnp.dtype(dtype).name)


# =============================================================================
# four chips (builder-run: python chip_smoke.py --chips 4)
# =============================================================================

def phase_placement(cluster, devices: Sequence[Any]) -> None:
    """``cluster`` as carve_tier_meshes places it on ``devices``, two
    requests per tier through /chat: nano alone on its chip, orin's
    weights sharded over ITS chips at total/tp bytes each, nothing of
    orin on nano's chip."""
    import jax

    from distributed_llm_tpu.parallel.mesh import (carve_tier_meshes,
                                                   describe_meshes)

    meshes = carve_tier_meshes(cluster, devices=devices)
    say("placement", "carve: " + describe_meshes(meshes))
    nano_devs = set(meshes["nano"].devices.flat)
    orin_devs = set(meshes["orin"].devices.flat)
    check(len(nano_devs) == 1 and not (nano_devs & orin_devs),
          "nano and orin must sit on disjoint chips")
    check(len(orin_devs) > 1, "orin was not given a multi-chip mesh")

    served = _bring_up("placement", cluster, devices)
    router = served.router
    try:
        for i, (msg, strategy, want) in enumerate([
                ("What is the capital of France?", "token", "nano"),
                ("Name three primary colours.", "token", "nano"),
                (LONG_SENTENCE * 40, "token", "orin"),
                (LONG_SENTENCE * 41, "token", "orin")]):
            _chat(served.client, served.record, router,
                  label=f"{want}#{i}", message=msg, strategy=strategy,
                  session=f"p{i}", expect_device=want)
        check(_tier_devices(router, "nano") == nano_devs
              and _tier_devices(router, "orin") == orin_devs,
              "the tiers' managers do not hold the carved devices")
        nano_bytes = _check_placement(router.nano.server_manager, "nano")
        orin_bytes = _check_placement(router.orin.server_manager, "orin")
        orin = _engine(router, "orin")
        tp = len(orin_devs)
        # Stacked [L, in, out] matmul weights are Megatron-sharded: each
        # chip of the mesh holds exactly 1/tp of every one of them.  The
        # embedding and the norms ride along whole.
        want_ids = {d.id for d in orin_devs}
        sharded = replicated = 0
        for leaf in jax.tree_util.tree_leaves(orin.params):
            shards = {s.device.id: s.data.nbytes
                      for s in leaf.addressable_shards}
            check(set(shards) == want_ids,
                  f"an orin weight has shards on {sorted(shards)}, the "
                  f"mesh is {sorted(want_ids)}")
            if leaf.ndim >= 3:
                check(all(n * tp == leaf.nbytes for n in shards.values()),
                      f"an orin {leaf.shape} weight is not split 1/{tp} "
                      f"per chip: {shards} of {leaf.nbytes} B")
                sharded += leaf.nbytes
            else:
                replicated += leaf.nbytes
        per_dev = sharded // tp + replicated
        say("placement", f"orin weights: {(sharded + replicated) / 2**30:.3f}"
                         f" GiB total, {per_dev / 2**30:.3f} GiB per chip "
                         f"over tp={tp} ({sharded / 2**30:.3f} GiB sharded "
                         f"1/{tp}, {replicated / 2**30:.3f} GiB replicated)")
        for d in devices:
            mem = d.memory_stats() or {}
            say("placement", f"device {d.id}: bytes_in_use="
                             f"{mem.get('bytes_in_use')} peak="
                             f"{mem.get('peak_bytes_in_use')}")
        (nano_dev,) = nano_devs
        in_use = (nano_dev.memory_stats() or {}).get("bytes_in_use")
        if in_use is not None:            # the CPU rehearsal reports none
            check(in_use < nano_bytes + min(orin_bytes // tp, 1 << 30),
                  f"nano's chip holds {in_use} B — more than nano's "
                  f"{nano_bytes} B: orin landed on it")
        say("placement", f"ok: nano {nano_bytes / 2**30:.2f} GiB on chip "
                         f"{nano_dev.id}, orin {orin_bytes / 2**30:.2f} GiB "
                         f"over chips {sorted(d.id for d in orin_devs)}")
    finally:
        phase_drain(served)


# tp=2 against tp=1, set before the run that tests it.  The two layouts
# differ only in where bf16 rounds: each of the 2 row-parallel matmuls of
# each layer adds two rounded partial sums instead of rounding one, 2^-8
# relative each, so the cached K/V of a 16-layer model drift apart by
# about sqrt(32) * 2^-8 = 2e-2 of their norm.  A mis-sharded weight, a
# missing all-reduce or permuted heads gives a relative error near 1.
TP_KV_REL_TOL = 0.05


def phase_tp_parity(tier, devices: Sequence[Any], prompts: Sequence[str]
                    ) -> Dict[str, Any]:
    """What the sharded path is compared with: the same preset, from the
    same seed, whole on one chip (tp=1) and sharded over two (tp=2).

    Compared through each engine's OWN compiled cold-prefill program:
    the K/V of every layer for every prompt must agree within
    ``TP_KV_REL_TOL`` (relative Frobenius error).  The greedy token
    streams are generated and reported too; they are identical on the
    CPU's virtual devices (tests/test_tp_parity.py pins that), but on
    the chip in bf16 with random weights the largest logit changes on
    rounding (measured, PR 22: 1 of 3 prompts identical), so tokens are
    a report here and not the criterion."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_tpu.engine.inference import prepare_prompt
    from distributed_llm_tpu.engine.manager import EngineManager
    from distributed_llm_tpu.parallel.mesh import tp_mesh

    def run(manager):
        t0 = time.perf_counter()
        manager.start_server()
        try:
            engine = manager.engine()
            held = _check_placement(manager, tier.model_preset)
            say("tp-parity",
                f"{tier.model_preset} up in {time.perf_counter() - t0:.1f} s"
                f" on device(s) "
                f"{sorted(d.id for d in _manager_devices(manager))} "
                f"({held / 2**30:.2f} GiB): tick="
                f"{'ragged fused' if engine.ragged else 'dense windowed'}")
            kv = []
            for prompt in prompts:
                ids, bucket = prepare_prompt(
                    engine.tokenizer, prompt, tier.prefill_buckets,
                    engine.cfg.max_seq_len, tier.max_new_tokens)
                padded = np.full((1, bucket), engine.tokenizer.pad_id,
                                 np.int32)
                padded[0, :len(ids)] = ids
                _, k_all, v_all = engine._prefill_fn(bucket)(
                    engine.params, jnp.asarray(padded),
                    jnp.asarray([len(ids)], np.int32),
                    jax.random.PRNGKey(0), jnp.float32(0.0))
                # Valid positions only: the padded tail is never read.
                kv.append(np.stack([
                    np.asarray(k_all, np.float32)[:, :len(ids)],
                    np.asarray(v_all, np.float32)[:, :len(ids)]]))
            toks = [tuple(engine.generate(p).token_ids) for p in prompts]
            return kv, toks
        finally:
            manager.stop_server()

    kv1, one = run(EngineManager(tier, devices=list(devices[:1])))
    kv2, two = run(EngineManager(tier, mesh=tp_mesh(devices, 2)))
    errs = []
    for prompt, ref, got, a, b in zip(prompts, kv1, kv2, one, two):
        rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        errs.append(rel)
        same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                    min(len(a), len(b)))
        say("tp-parity",
            f"{prompt[:40]!r}: K/V of {ref.shape[1]} layers x "
            f"{ref.shape[2]} positions rel err {rel:.2e} "
            f"(tol {TP_KV_REL_TOL:g}); tokens {len(a)} at tp=1, {len(b)} "
            f"at tp=2, "
            + ("identical" if a == b else f"different from token {same}"))
        check(np.isfinite(got).all() and rel <= TP_KV_REL_TOL,
              f"tp=2 K/V differ from tp=1 for {prompt[:40]!r}: relative "
              f"error {rel:.3e} > {TP_KV_REL_TOL:g}")
    check(all(len(t) > 0 for t in one + two),
          "tp parity generated no tokens")
    return {"kv_rel_err": errs, "tokens_tp1": one, "tokens_tp2": two}


def phase_four_chips() -> None:
    import jax

    from distributed_llm_tpu.config import ClusterConfig, TierConfig
    devices = jax.devices()
    phase_placement(ClusterConfig(), devices)
    tier = TierConfig(name="orin", model_preset="orin_bench", tp=2,
                      decode_batch=4, max_new_tokens=32,
                      prefill_buckets=(256,), enable_prefix_cache=False)
    phase_tp_parity(tier, devices[1:3],
                    ["short question about rivers please",
                     "long question: "
                     + "rivers lakes mountains oceans deltas " * 8,
                     "what is the tallest mountain in asia today"])


# =============================================================================

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the four-chip placement check and "
                         "the tp=2 vs tp=1 comparison (builder-run)")
    ap.add_argument("--config", metavar="NAME",
                    help="runs ONLY the pool phase, on the tiers of the "
                         "benchmark configuration benchmark/configs/"
                         "NAME.json as the benchmark builds them")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    if args.config:
        served = bench_served(args.config)
        try:
            phase_pool_programs(served, list(served.entries))
        finally:
            served.drain()
    elif args.chips == 4:
        phase_four_chips()
    else:
        import jax
        served = phase_serve(smoke_cluster(), devices=jax.devices()[:1])
        try:
            phase_what_ran(served)
            phase_pool_programs(served)
        finally:
            phase_drain(served)
        phase_kernels()
    say("done", f"all phases passed in {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
