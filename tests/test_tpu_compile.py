"""The main path's kernels and decode tick COMPILE for a v5e, as tests.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (on-chip-measurement guide §2): what it would
refuse on the chip — a misaligned slice, too much VMEM, a kernel Mosaic
cannot lower — it refuses here, at no chip time.  A compile that passes
is not a chip run: nothing executes, so no result and no time comes from
this file (chip_smoke.py compares the same kernels with their references
on the chip).

The topology is described inside module-scoped fixtures OF THIS FILE and
nowhere at import time: only one process may load the TPU library, every
xdist worker imports every test file, and only the worker that is handed
this file may load it.  Everything compiles in this process, with the
persistent compile cache off (an entry written for a described chip
cannot be read back without one).
"""

from __future__ import annotations

import dataclasses
import math
import os
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (repo-root script: the kernel case table)

from distributed_llm_tpu.config import (MODEL_PRESETS,  # noqa: E402
                                        flagship_cluster)
from distributed_llm_tpu.obs.program_scopes import (  # noqa: E402
    HLO_RESULT, pool_sized, row_major)

KERNELS = ["flash_causal_attention",
           # The served MHA tick's attention over the whole token-major
           # pool (a K/V head to every query head, whatever the preset's
           # own grouping: the kernel serves no other).
           "paged_rows_decode_attention",
           # The routed experts' grouped product (no heads in it: the
           # same case under every preset): an up and a down product at
           # the decode ticks' rows and the widths the cells store.
           "grouped_product.wide-reasoning",
           "grouped_product.reasoned-reply",
           # An expert layer's whole FFN as ONE call (ISSUE 53): no gate
           # at wide-reasoning's widths, gated at reasoned-reply's.
           "grouped_ffn.wide-reasoning",
           "grouped_ffn.reasoned-reply",
           # Mamba-1's recurrence over a chunk (float32 and headless: the
           # same case under every preset), at the shared-K/V cell's chunk.
           "ssm_chunk_scan",
           # The latent row's chunk attention by blocks of the window
           # (its own 64 heads: the same case under every preset), at a
           # rung of sarvam-105b's lane.
           "latent_chunk_attention"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off around
    every compile of this module."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer ``_interpret`` from the test: jax.default_backend() is still
    'cpu' here, and the kernels must lower for Mosaic, not the
    interpreter."""
    from distributed_llm_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_case_table_is_the_kernel_list():
    cfg = MODEL_PRESETS["nano_1b"]
    assert list(chip_smoke.kernel_cases(
        cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, jnp.bfloat16)
    ) == KERNELS


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("preset,dtype,precision", [
    ("nano_1b", jnp.bfloat16, None),      # Nq 32, Nkv 8, D 64, as served
    ("orin_8b", jnp.bfloat16, None),      # D 128
    ("nano_1b", jnp.float32, "highest"),  # what chip_smoke's f32 pin runs
], ids=["nano_1b-bf16", "orin_8b-bf16", "nano_1b-f32-highest"])
def test_kernel_compiles_for_v5e(one_chip, compiled_kernels, preset, dtype,
                                 precision, kernel):
    cfg = MODEL_PRESETS[preset]
    case = chip_smoke.kernel_cases(cfg.num_heads, cfg.num_kv_heads,
                                   cfg.head_dim, dtype)[kernel]
    shapes = _on(one_chip, jax.eval_shape(case.make_args))
    with jax.default_matmul_precision(precision or "default"):
        compiled = jax.jit(case.pallas).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


_COMPILED = {}


def _pool_program(one_chip, tier, program):
    """``_compile_pool_program``, once a (tier, program) of this process:
    the tests below read a compiled program, its engine and its pool
    shapes and change none of them, and several hold the same program to
    different things (ISSUE 57's pool cases take what the others
    compiled)."""
    key = (tier, tuple(program))
    if key not in _COMPILED:
        _COMPILED[key] = _compile_pool_program(one_chip, tier, program)
    return _COMPILED[key]


def _compile_pool_program(one_chip, tier, program):
    """One of the engine's own pool programs, compiled for the described
    chip, lowered on shapes: ``jax.eval_shape`` weights (nothing of the
    model materializes) and a pool of shapes at the tier's real size,
    beside an engine whose own pool — real host memory — stays tiny.
    ``program`` is ("decode", window), ("chunk", chunk, window) or
    ("cow",).  Gives (engine, pool shapes, compiled, pool argument)."""
    from distributed_llm_tpu import models
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    from distributed_llm_tpu.engine.paged_kv import PagedConfig, init_pool

    cfg = tier.model()
    params = _on(one_chip, jax.eval_shape(
        partial(models.init_params, cfg, seed=0)))
    paged = PagedConfig(block_size=tier.kv_block_size,
                        max_slots=tier.decode_batch,
                        max_seq_len=cfg.max_seq_len,
                        pool_blocks=tier.kv_pool_blocks)
    pool = _on(one_chip, jax.eval_shape(
        lambda: init_pool(cfg, paged, tier.kv_quantize)))
    tiny = max(tier.prefill_buckets) // tier.kv_block_size + 2
    engine = ContinuousBatchingEngine(
        dataclasses.replace(tier, kv_pool_blocks=tiny), params=params)
    try:
        kind, *sizes = program
        (compiled, pool_arg), = chip_smoke.pool_programs(
            engine, pool, sizes if kind == "decode" else [],
            [tuple(sizes)] if kind == "chunk" else [],
            cow=kind == "cow").values()
    finally:
        engine.stop()
    return engine, pool, compiled, pool_arg


def _nano_tick(one_chip):
    """The batched engine's own decode-tick program at nano_1b and full
    KV residency, built the way the chip builds it."""
    # Compiled anew each time: the environment of the test, and not the
    # tier, says which tick this is.
    engine, _, compiled, _ = _compile_pool_program(
        one_chip, flagship_cluster(n_devices=1).nano, ("decode", 256))
    return engine, compiled


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The engine asks jax.default_backend() which path to take; here
    the answer is steered to the chip's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for var in ("DLLM_RAGGED", "DLLM_ATTENTION"):
        monkeypatch.delenv(var, raising=False)


def test_default_nano_tick_on_tpu_is_dense_windowed_xla(one_chip, as_on_tpu):
    """The chip's default path at nano_1b's GQA widths: the dense
    windowed tick with the XLA gather — no kernel in the program
    (chip_smoke.py's what-ran lines say the same of the attached
    chip)."""
    engine, compiled = _nano_tick(one_chip)
    assert engine.ragged is False and engine.spec is False
    assert engine.cfg.attention_impl == "pallas"
    assert "tpu_custom_call" not in compiled.as_text()


def test_forced_fused_nano_tick_compiles_for_v5e(one_chip, as_on_tpu,
                                                 monkeypatch):
    """The fused tick, which the chip does not take by itself (ROADMAP
    D3): forced, it lowers for the chip, and over GQA's narrow rows it
    attends in the XLA form like the windowed one."""
    monkeypatch.setenv("DLLM_RAGGED", "1")
    engine, compiled = _nano_tick(one_chip)
    assert engine.ragged is True
    assert engine.decode_attention_form() == "merged"
    assert "tpu_custom_call" not in compiled.as_text()


def test_the_chunk_scan_compiles_at_the_longest_chunk_it_serves(
        one_chip, compiled_kernels):
    """``ssm_chunk_scan.serves`` counts the blocks' bytes against the
    kernel's VMEM; here the chip's compiler takes the longest chunk that
    count admits at the benchmark's widths (576 positions since PR 47),
    and the count refuses the next tile of 8."""
    from distributed_llm_tpu.ops import ssm_chunk_scan
    _, n, inner = chip_smoke.SCAN_SHAPE
    steps = max(t for t in range(8, 4096, 8)
                if ssm_chunk_scan.serves(t, n, inner))
    assert steps == 576
    case = chip_smoke.kernel_cases(1, 1, 64, jnp.float32,
                                   scan=(steps, n, inner))["ssm_chunk_scan"]
    shapes = _on(one_chip, jax.eval_shape(case.make_args))
    compiled = jax.jit(case.pallas).lower(*shapes).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# -- the KV pool stays in place (ISSUE 27) -------------------------------------

def _bench_tier(monkeypatch, config: str = "smollm2-1.7b"):
    """A benchmark configuration's own nano tier, read from its file the
    way benchmark/cluster.py builds it (SmolLM2-1.7B: 24 layers, 32/32
    heads, head_dim 64, 144 + 1 blocks of 64 tokens, 8 slots, 4 steps a
    tick)."""
    from distributed_llm_tpu.config import TierConfig
    cluster, entries = chip_smoke.benchmark_cluster(config)
    entry = cluster.tier_entries(entries, False)["nano"]
    monkeypatch.setitem(MODEL_PRESETS, entry["preset"],
                        cluster.program_config(entry))
    kw = dict(entry["tier"], prefill_buckets=tuple(
        entry["tier"]["prefill_buckets"]))
    return TierConfig(name="nano", model_preset=entry["preset"], **kw)


def _flagship_nano(preset):
    return dataclasses.replace(flagship_cluster(n_devices=1).nano,
                               model_preset=preset)


GB = 1e9
# (tier, program, temporaries allowed in GB, the tick's attention form).
# At the benchmark's sizes the commit before PR 27 compiled to 11.30 GB of
# temporaries for a tick and 3.51 for a chunk program, with 6 pool-sized
# copies, 2 update-slices and 2 slice fusions (compile for a described
# v5e, PR 27); ISSUE 27 asked for under 1 GB and no pool-sized move at
# all.  The served ticks are held to what they compile to: 0.0005-0.0006
# GB at both SmolLM2 rungs and for GQA at head_dim 64 and 128 (the window
# itself is no temporary in either form: XLA kept PR 30's gathered rows
# in VMEM, PR 45's kernel never has them; until PR 48 the entry's two
# re-laid copies of the wq and wk stacks stood here, 0.4034 GB at
# SmolLM2's widths and 0.135 for GQA: the test after the routed ticks'
# holds them gone).  ``streamed`` (ISSUE 45): a K/V head to
# every query head, the block table walked by the kernel of
# ops/rows_attention.py; ``merged``: GQA, whose narrow rows the kernel
# loses on (``rows_attention.serves``), keeps the XLA gather.
POOL_PROGRAMS = {
    "smollm2-decode-256":
        (_bench_tier, ("decode", 256), 0.01, "streamed"),
    "smollm2-decode-2048":
        (_bench_tier, ("decode", 2048), 0.01, "streamed"),
    "smollm2-chunk-256-256":
        (_bench_tier, ("chunk", 256, 256), 1.0, None),
    "smollm2-chunk-256-1024":
        (_bench_tier, ("chunk", 256, 1024), 1.0, None),
    # A rung of the lane's doubling ladder (ISSUE 42): what a chunk that
    # ends at 1280-2048 runs where it ran the span's program, 8192.
    "smollm2-chunk-256-2048":
        (_bench_tier, ("chunk", 256, 2048), 1.0, None),
    "smollm2-copy_block":
        (_bench_tier, ("cow",), 1.0, None),
    "nano_1b-gqa-decode-256":
        (lambda _: _flagship_nano("nano_1b"), ("decode", 256), 0.01,
         "merged"),
    "orin_bench-d128-decode-256":
        (lambda _: _flagship_nano("orin_bench"), ("decode", 256), 0.01,
         "merged"),
}


def window_passes(hlo: str, window_elements: int, fusions: bool = False):
    """``(result, opcode)`` of every instruction of the attention scope
    that produces ``window_elements`` elements or more, other than by
    handing a buffer on: the head-split relayout of a gathered window
    (``reshape`` to ``[B, S, N_kv, D]``), its padded transposes
    (``copy``), the group ``broadcast`` of a GQA window.  Outside any
    fusion by default (a fusion's insides are the compiler's business:
    what PR 30's merged form is held to); with ``fusions``, inside them
    too and the fusions themselves (ISSUE 45: the ``gather`` of the
    window, which stands in a fusion of its own, and whatever it hands
    the products)."""
    handed_on = ("parameter", "get-tuple-element", "bitcast")
    found, computation = [], ""
    for line in hlo.splitlines():
        if line.endswith("{") and " = " not in line:
            words = line.split()
            computation = words[1 if words[0] == "ENTRY" else 0]
            continue
        m = HLO_RESULT.match(line)
        if (not m or "/attention/" not in line or m[3] in handed_on
                or not fusions and ("fused_computation" in computation
                                    or m[3] == "fusion")):
            continue
        dims = m[2][m[2].index("[") + 1:-1]
        if math.prod(int(x) for x in dims.split(",") if x) >= window_elements:
            found.append((m[2], m[3]))
    return found


@pytest.mark.parametrize("case", list(POOL_PROGRAMS))
def test_pool_program_leaves_the_pool_in_place(one_chip, as_on_tpu,
                                               monkeypatch, case):
    """Every program that takes the pool updates the one buffer in place:
    the pool aliased input to output, in the same format on both sides
    (the device's default: nothing is pinned, so a program loaded from
    the persistent compile cache agrees), nothing pool-shaped produced
    but by an in-place write (no ``copy``, no stacked ``ys``, no layer
    slice), temporaries small.  The served XLA tick (ISSUE 30) contracts
    over the merged ``N_kv * D`` axis: the gather's result reaches the
    two products as a ``bitcast``, nothing window-sized (``B × S × N_kv
    × D`` elements) is produced outside a fusion between the pool and
    the softmax — at head_dim 64 (MHA at both of the benchmark's rungs,
    GQA) and at head_dim 128.  Where every query head has a K/V head of
    its own (ISSUE 45) the tick's attention is the kernel that walks the
    block table, handed the pool WHOLE, and nothing window-sized is
    produced inside a fusion either."""
    make_tier, program, temp_limit_gb, form = POOL_PROGRAMS[case]
    engine, pool, compiled, pool_arg = _pool_program(
        one_chip, make_tier(monkeypatch), program)
    assert engine.ragged is False
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == int(form == "streamed")
    assert ("paged_rows_decode" in text) is (form == "streamed")
    if form is not None:
        assert engine.decode_attention_form(program[1]) == form
    facts = chip_smoke.pool_program_facts(compiled, pool_arg, pool)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    assert facts["formats_match"], facts
    # Aliased whole, and no padding: the merged head axis fills the lanes.
    assert facts["alias_bytes"] == pool_bytes, facts
    assert facts["output_bytes"] - facts["alias_bytes"] < 1 << 20, facts
    assert facts["pool_sized_moves"] == {}, facts
    assert facts["temp_bytes"] < temp_limit_gb * GB, facts
    if program[0] != "cow":
        # The loop structure the benchmark files programs by: steps and
        # layers for a tick, layers alone for a chunk program.
        assert text.count(" while(") == (2 if program[0] == "decode" else 1)
    if form in ("streamed", "merged"):
        cfg = engine.cfg
        window = (engine.paged.max_slots * program[1]
                  * cfg.num_kv_heads * cfg.head_dim)
        assert window_passes(text, window) == []
        # The streamed tick has nothing window-sized in its attention
        # scope at all, inside a fusion or outside one (on PR 42's tree
        # this finds the two gathers and what they hand the products);
        # the merged one still gathers its window, twice.
        inside = window_passes(text, window, fusions=True)
        if form == "streamed":
            assert inside == []
        else:
            assert sum(op == "gather" for _, op in inside) == 2, inside


# -- a pool array has one format (ISSUE 57) ------------------------------------

# Every benchmark configuration's tick and one chunk program, at the real
# sizes.  The programs other tests of this file compile are taken from
# them (``_pool_program`` keeps what it compiled); compiled for these
# cases alone: the tick of ``kimi-linear-48b-a3b`` at its cell's rung,
# 5120, ``jamba2-3b``'s two, the chunk programs of ``xing4.0-29b-a4b``
# and ``zaya1-8b`` and ``sarvam-105b``'s tick at 16 640.
BENCH_POOL_PROGRAMS = [
    ("smollm2-1.7b", ("decode", 256)),
    ("smollm2-1.7b", ("chunk", 256, 1024)),
    ("xing4.0-29b-a4b", ("decode", 256)),
    ("xing4.0-29b-a4b", ("chunk", 256, 4096)),
    ("nemotron-3-nano-30b-a3b", ("decode", 256)),
    ("nemotron-3-nano-30b-a3b", ("chunk", 256, 1024)),
    ("phi-4-mini-flash-reasoning", ("decode", 5120)),
    ("phi-4-mini-flash-reasoning", ("chunk", 256, 5120)),
    ("jamba2-3b", ("decode", 256)),
    ("jamba2-3b", ("chunk", 256, 2048)),
    ("zaya1-8b", ("decode", 256)),
    ("zaya1-8b", ("chunk", 256, 5120)),
    ("kimi-linear-48b-a3b", ("decode", 256)),
    ("kimi-linear-48b-a3b", ("decode", 5120)),
    ("kimi-linear-48b-a3b", ("chunk", 256, 5120)),
    # The tick at the cell's furthest rung and the chunk program at its
    # widest (ISSUE 59): 64 latent heads over 16 384 positions.
    ("sarvam-105b", ("decode", 16640)),
    ("sarvam-105b", ("chunk", 256, 16384)),
]
# The pool-sized arrays (16 MiB or more: ``POOL_SIZED_BYTES``) a
# configuration's pool must hold, so that a case cannot pass on a pool
# the floor hides.  The conv tails ``t`` (8.3 MB at most) and ``owner``
# lie under it: every hybrid configuration's ``t`` rests with the slots
# and not the taps second-minor and is copied in and out of every
# program, 0.03 ms a tick (PERF.md section 7).
POOL_SIZED = {"smollm2-1.7b": {"k", "v"},
              "xing4.0-29b-a4b": {"c"},
              "nemotron-3-nano-30b-a3b": {"k", "v", "s"},
              "phi-4-mini-flash-reasoning": {"k", "v", "rk", "rv", "s"},
              "jamba2-3b": {"k", "v", "s"},
              "zaya1-8b": {"k", "v"},
              "kimi-linear-48b-a3b": {"c", "s"},
              # No row kind: "s" and "t" are zero layers deep.
              "sarvam-105b": {"c"}}


# What a program may still move, and why it is not this rule's to take:
# ``jamba2-3b``'s tick (4 slots) keeps its whole state array, 34 MB, in
# the chip's other memory space for the tick's four steps: the compiler's
# memory-space assignment copies it there at the entry and back at the
# end (an async ``copy-start`` / ``copy-done`` pair each way, the format
# the same on both sides), on PR 56's tree as on this one.
KNOWN_MOVES = {("jamba2-3b", ("decode", 256)): {"copy-done": 2}}


@pytest.mark.parametrize(
    "config,program", BENCH_POOL_PROGRAMS,
    ids=[f"{c}-{'-'.join(map(str, p))}" for c, p in BENCH_POOL_PROGRAMS])
def test_a_pool_array_has_one_format(one_chip, as_on_tpu, monkeypatch,
                                     config, program):
    """Compiled for a described v5e, every configuration's tick and chunk
    program takes and returns every pool-sized array ROW-MAJOR, the
    format the device rests it in by default (nothing is pinned, so a
    program loaded from the persistent compile cache agrees), aliased,
    and holds no pool-sized ``copy``: neither at its edge nor round a
    loop.  On PR 56's tree ``kimi-linear-48b-a3b`` fails all three of its
    cases, and no other configuration any: its latent pool
    ``bf16[2, 1281, 64, 576]`` rested block-minor (padding 1281 blocks to
    1408 is more compact than padding 576 lanes to 640) and was copied
    into row-major and back by every tick (``pool_sized_moves`` ``{copy:
    2}``) and every chunk program, which also carried its state array
    ``f32[7, 16, 32, 128, 128]`` round the layer loop in the operand order
    of ``kda_scan``'s products (``{copy: 4}``).  Since ISSUE 57 the latent
    row rests 640 wide (``cfg.cache_row_rest_width``) and the state goes
    back into the carry row-major (``hybrid_ssm._row_major``)."""
    tier = _bench_tier(monkeypatch, config)
    engine, pool, compiled, pool_arg = _pool_program(one_chip, tier, program)
    sized = {key for key, x in pool.items() if pool_sized(x)}
    assert sized == POOL_SIZED[config]
    if engine.cfg.kv_lora_rank:
        assert pool["c"].shape[-1] == engine.cfg.cache_row_rest_width == 640
        assert engine.cfg.cache_row_width == 576
    facts = chip_smoke.pool_program_facts(compiled, pool_arg, pool)
    assert facts["formats_match"], facts
    assert facts["pool_sized_moves"] == KNOWN_MOVES.get(
        (config, program), {}), facts
    for key in sized:
        assert row_major(facts["major_to_minor"][key]), (key, facts)
    # Everything large that comes back is the pool, aliased; the device's
    # tiles pad no pool-sized array (the latent row says its padding in
    # its shape), so the aliased bytes are the arrays' own but for the
    # small ones' tiles.
    pool_bytes = sum(x.size * x.dtype.itemsize for x in pool.values())
    assert facts["output_bytes"] - facts["alias_bytes"] < 1 << 20, facts
    assert pool_bytes <= facts["alias_bytes"] < pool_bytes + (8 << 20), facts


# -- the routed experts stay where they rest (ISSUE 34) ------------------------

# configuration: (kernel calls in the tick's one layer body: ONE an expert
# layer since ISSUE 53, the stacked experts of one key in GB, temporaries
# allowed in GB[, what a CHUNK's experts trace: the one fused call but
# where its matrices and rows pass the kernel's VMEM]).  PR 33's
# trap: at a minor width off the lanes the entry of every program copied
# every held expert ([2, 64, 2688, 1856] x 3: 4.36 GB of temporaries).
ROUTED_TICKS = {
    "xing4.0-29b-a4b": (1, 2.35, 0.5),
    "nemotron-3-nano-30b-a3b": (3, 1.32, 0.5),
    # Top-1 of 16 gated experts of 2048 x 2048 over 20 layers (PR 51).
    "zaya1-8b": (1, 2.68, 0.5),
    # 64 held of 256 gated experts of 2304 x 1024 over 8 expert sublayers,
    # four a period of "KEKELEKE" (PR 54).
    "kimi-linear-48b-a3b": (4, 0.60, 0.5),
    # 32 held of 128 gated experts of 4096 x 2048 over 5 expert sublayers,
    # one a period of "LE" (PR 59); the temporaries are a period's slices
    # of the scanned matrices (wq 100 MB, wo 67, the shared expert 50) and
    # the lead MLP's 403 MB beside them, read at 0.73 GB.  A chunk's 2048
    # assignments against three matrices of 4096 x 2048 pass the fused
    # call's VMEM (``grouped_product.serves_ffn``): a call a product.
    "sarvam-105b": (1, 2.68, 0.9, "pallas"),
}


@pytest.mark.parametrize("config", list(ROUTED_TICKS))
def test_routed_tick_reads_the_experts_where_they_rest(one_chip, as_on_tpu,
                                                       monkeypatch, config):
    """The decode ticks of the benchmark's routed-expert
    configurations, at their real sizes: every expert layer of the
    layer body is ONE call of the repo's kernel, handed the STACKED
    experts whole — no copy of them at the program's entry, no per-layer
    slice: the temporaries stay far under one key's stack."""
    products, stack_gb, temp_limit_gb, *chunk = ROUTED_TICKS[config]
    tier = _bench_tier(monkeypatch, config)
    engine, _, compiled, _ = _pool_program(one_chip, tier, ("decode", 256))
    assert engine.grouped_product_form() == {
        "decode": "pallas_ffn", "prefill": (chunk or ["pallas_ffn"])[0]}
    # The hybrid family's attention layers are GQA 32/2 at head 128: rows
    # of 512 B, which ``rows_attention.serves`` leaves to the XLA form
    # (ISSUE 45); the latent family attends in code of its own.
    assert engine.decode_attention_form(256) == (
        "latent" if engine.cfg.kv_lora_rank else "merged")
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == products
    assert "grouped_product_ffn" in text and "ragged-dot" not in text
    temp_gb = compiled.memory_analysis().temp_size_in_bytes / GB
    assert temp_gb < temp_limit_gb < stack_gb, temp_gb
    # The kernel is one operation of the layer body: the tick keeps the
    # two nested loops the benchmark files it by (steps, layers).
    assert text.count(" while(") == 2


@pytest.mark.parametrize("program", [("decode", 256), ("chunk", 256, 1024)],
                         ids=["tick", "chunk"])
def test_whole_lane_widths_rest_as_stored(one_chip, as_on_tpu, monkeypatch,
                                          program):
    """``nemotron-3-nano-30b-a3b``'s experts at 2688 x 1920 (ISSUE 55:
    whole lane-widths, no longer multiples of 256): the tick and a chunk
    program take both stacks in the layout they are stored in (F minor
    for up, H minor for down) and no operation of either program makes an
    array of expert matrices — the trap of the published 1856, where the
    device rested ``we_up`` transposed and every program copied every
    held expert at its entry (PR 33)."""
    from distributed_llm_tpu.models import hybrid_ssm
    tier = _bench_tier(monkeypatch, "nemotron-3-nano-30b-a3b")
    engine, _, compiled, _ = _pool_program(one_chip, tier, program)
    assert hybrid_ssm.expert_dims_stored(engine.cfg) == (2688, 1920)
    up, down = hybrid_ssm.expert_stacks(engine.params)
    assert up.shape == (2, 64, 2688, 1920)
    assert down.shape == (2, 64, 1920, 2688)
    leaves = jax.tree_util.tree_flatten_with_path(engine.params)[0]
    formats = jax.tree.leaves(compiled.input_formats[0][0])
    stacks = [fmt for (path, _), fmt in zip(leaves, formats)
              if jax.tree_util.keystr(path).endswith(("['we_up']",
                                                      "['we_down']"))]
    assert len(stacks) == 6                  # three ``E`` positions a period
    assert all(fmt.layout.major_to_minor == (0, 1, 2, 3) for fmt in stacks)
    results = filter(None, map(HLO_RESULT.match,
                               compiled.as_text().splitlines()))
    made = [(m[2], m[3]) for m in results
            if m[2].endswith(("2688,1920]", "1920,2688]"))
            and m[3] not in ("parameter", "get-tuple-element", "bitcast")]
    assert made == []
    assert engine.grouped_product_form() == {"decode": "pallas_ffn",
                                            "prefill": "pallas_ffn"}
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * GB


def test_a_pattern_with_a_lead_keeps_one_layer_loop_at_the_real_sizes(
        one_chip, as_on_tpu, monkeypatch):
    """``kimi-linear-48b-a3b``'s chunk program at its real sizes: the lead
    "K-" runs inline and the periods "KEKELEKE" x 2 are the program's ONE
    ``while`` (the tick's two are held by the routed-tick test above) —
    neither the lead nor the linear-attention chunk recurrence (its
    triangular inverse, its sub-blocks) lowers to a loop, so the
    benchmark files the program as a prefill and not as a tick; and the
    matrix form's temporaries stay under a gigabyte."""
    tier = _bench_tier(monkeypatch, "kimi-linear-48b-a3b")
    engine, _, compiled, _ = _pool_program(one_chip, tier,
                                           ("chunk", 256, 5120))
    assert engine.cfg.layer_lead == "K-"
    assert engine.cfg.layer_period == "KEKELEKE"
    text = compiled.as_text()
    assert text.count(" while(") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * GB
    for scope in ("kda_proj", "kda_conv", "kda_gate", "kda_scan",
                  "kda_out_norm", "latent_attention", "kv_write"):
        assert scope in text, scope


def test_a_pattern_of_latent_layers_alone_compiles_at_its_widest_rung(
        one_chip, as_on_tpu, monkeypatch):
    """``sarvam-105b``'s chunk program at its real sizes and widest rung
    (ISSUE 59): the lead "L-" runs inline and the periods "LE" x 5 are the
    program's ONE ``while`` (the tick's two are held by the routed-tick
    test above); 64 heads' float32 scores against 16 384 positions, their
    probabilities and the up-projected keys and values of the whole rung
    were the plain form's temporaries, 1.64 GB a layer in turn (PR 59);
    by blocks of the window (ISSUE 61) the gathered rows, 21 MB, are the
    attention's largest, and weights, pool and temporaries stay under 12.5
    GB of the chip's 16 GiB; the sines of a chunk's positions are made
    once, under ``step_inputs``."""
    tier = _bench_tier(monkeypatch, "sarvam-105b")
    engine, pool, compiled, _ = _pool_program(one_chip, tier,
                                              ("chunk", 256, 16384))
    cfg = engine.cfg
    assert (cfg.layer_lead, cfg.layer_period) == ("L-", "LE")
    assert cfg.rotary and cfg.num_heads == 64 and cfg.rope_factor == 40.0
    assert pool["s"].size == pool["t"].size == 0
    text = compiled.as_text()
    assert text.count(" while(") == 1
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 1.0 * GB
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) < 12.5 * GB
    for scope in ("latent_attention", "kv_write", "mixer_proj",
                  "moe_router", "moe_experts", "shared_expert", "ffn",
                  "step_inputs", "head"):
        assert scope in text, scope
    # gate, up, down; the latent attention of the lead layer and of the
    # scan's body.
    assert text.count("tpu_custom_call") == 3 + 2
    assert "ragged-dot" not in text


# -- the latent chunk attention by blocks of the window (ISSUE 61) -------------

# The chunk program at the widest rung each latent cell's requests reach
# (``reasoned-reply``'s prompts end under 4096), compiled by the pool
# cases above: (heads, what else the program calls a kernel for).
LATENT_CHUNKS = {
    ("sarvam-105b", ("chunk", 256, 16384)): (64, 3),
    ("xing4.0-29b-a4b", ("chunk", 256, 4096)): (32, 1),
}


@pytest.mark.parametrize("config,program", list(LATENT_CHUNKS),
                         ids=[c for c, _ in LATENT_CHUNKS])
def test_the_latent_chunk_attention_leaves_no_scores_in_memory(
        one_chip, as_on_tpu, monkeypatch, config, program):
    """One custom call a latent site (the inline lead layer, the scan's
    body) and nothing ``[N, S, W]``-sized, inside a fusion or outside one:
    neither the float32 scores, nor their probabilities, nor (at a head's
    256 numbers a row, as many elements) the up-projected window.  On the
    parent the scores alone were 1.07 GB a layer at sarvam's top rung."""
    heads, other_calls = LATENT_CHUNKS[config, program]
    tier = _bench_tier(monkeypatch, config)
    engine, pool, compiled, _ = _pool_program(one_chip, tier, program)
    _, chunk, window = program
    assert engine.cfg.num_heads == heads
    assert engine.chunk_attention_form(chunk, window) == "blocks"
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == other_calls + 2
    assert sum("latent_chunk_attention" in line for line in calls) == 2
    large, scoped = [], 0
    for line in text.splitlines():
        m = HLO_RESULT.match(line)
        if not m or "/latent_attention/" not in line:
            continue
        scoped += 1
        dims = m[2][m[2].index("[") + 1:-1]
        if math.prod(int(x) for x in dims.split(",") if x) >= (
                heads * chunk * window):
            large.append((m[2], m[3]))
    assert scoped and large == []


def test_no_other_program_reaches_the_latent_chunk_kernel(
        one_chip, as_on_tpu, monkeypatch):
    """Every configuration's tick, and the chunk program of the five that
    cache no latent row, lower without ``serves`` being asked: the
    absorbed decode form and the dense, ring, shared-K/V, one-head and
    CCA attention have call sites of their own, so their programs are the
    parent's.  Lowered anew at a rung no case above compiles (a ``jit``
    does not trace a shape twice)."""
    from distributed_llm_tpu.ops import latent_chunk_attention

    def asked(*shape):
        raise AssertionError(f"latent_chunk_attention.serves{shape}")
    monkeypatch.setattr(latent_chunk_attention, "serves", asked)
    lowered = 0
    for config in dict.fromkeys(c for c, _ in BENCH_POOL_PROGRAMS):
        tier = _bench_tier(monkeypatch, config)
        engine, pool, _, _ = _pool_program(
            one_chip, tier, next(p for c, p in BENCH_POOL_PROGRAMS
                                 if c == config))
        engine._decode_fn = None
        assert engine.lower_pool_program(
            "decode", 512 // tier.kv_block_size, pool) is not None
        lowered += 1
        if not engine.cfg.kv_lora_rank:
            assert engine.chunk_attention_form(256, 512) is None
            assert engine.lower_pool_program(
                "chunk_prefill", (256, 512), pool) is not None
            lowered += 1
    assert lowered == 8 + 5
    # The control: a latent configuration's chunk program asks.
    with pytest.raises(AssertionError, match="latent_chunk_attention.serves"):
        engine.lower_pool_program("chunk_prefill", (256, 512), pool)


# -- wq and wk are read where they rest (ISSUE 48) -----------------------------

def weight_sized_moves(hlo: str, least_bytes: int):
    """``(name, result)`` of every ``copy`` and every slice fusion (a
    ``fusion`` whose root is a ``dynamic-slice``) that stands as an
    operation of its own — outside any fused computation — and whose
    result holds ``least_bytes`` or more: a matrix written out, where a
    product that reads the stack in place has the slice INSIDE its
    fusion."""
    fused = set(re.findall(r" fusion\(.*? calls=%?([\w.-]+)", hlo))
    rows, roots, computation = [], {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and " = " not in line:
            words = line.split()
            computation = words[1 if words[0] == "ENTRY" else 0].lstrip("%")
            continue
        m = HLO_RESULT.match(line)
        if not m:
            continue
        if m[1]:
            roots[computation] = m[3]
        if computation not in fused and m[3] in ("copy", "fusion"):
            rows.append((line.split(" = ")[0].split()[-1], m[2], m[3], m[4]))
    found = []
    for name, result, op, rest in rows:
        called = re.search(r"calls=%?([\w.-]+)", rest)
        if op == "fusion" and roots.get(called[1]) != "dynamic-slice":
            continue
        kind, dims = result[:-1].split("[")
        bits = re.search(r"\d+$", kind)            # bf16, f32, s8; pred: 8
        elements = math.prod(int(x) for x in dims.split(",") if x)
        if elements * (int(bits[0]) if bits else 8) // 8 >= least_bytes:
            found.append((name, result))
    return found


# The tick at both of the benchmark's rungs and one continuation chunk
# program.  The chunk program's two head-split copies of the gathered
# window (``bf16[1024, 32, 64]``, 4.2 MB: ROADMAP S6) stand on both sides
# of ISSUE 48, under the matrix's size at this rung and inside fusions.
DENSE_PROGRAMS = {"decode-256": ("decode", 256),
                  "decode-2048": ("decode", 2048),
                  "chunk-256-1024": ("chunk", 256, 1024)}


@pytest.mark.parametrize("program", list(DENSE_PROGRAMS))
def test_dense_programs_read_wq_and_wk_where_they_rest(one_chip, as_on_tpu,
                                                       monkeypatch, program):
    """SmolLM2-1.7B's own tick and chunk program at their real sizes (24
    x ``[2048, 2048]``, 32/32 heads of 64) hold no ``copy`` and no slice
    fusion as large as one layer's ``wq`` (8.4 MB): the q and k products
    read the stacks in place, as ``wv``'s does.  On PR 47's tree each
    program holds four — the tick the entry's two re-laid copies of the
    whole stacks (``bf16[24, 2048, 2048]{1,2,0}``, 0.40 GB of
    temporaries) and a layer's two ``constant_dynamic-slice_fusion`` out
    of them, the chunk program the two slice fusions and a transposing
    ``copy`` of each — because the head split's layout travelled through
    the product into the weight; ``transformer.project_qkv`` pins the
    product's dense rows before the split (compile for a described v5e,
    PR 48)."""
    tier = _bench_tier(monkeypatch)
    engine, _, compiled, _ = _pool_program(one_chip, tier,
                                           DENSE_PROGRAMS[program])
    cfg = engine.cfg
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.num_layers) == (2048, 32, 32, 64, 24)
    matrix = cfg.hidden_size * cfg.num_heads * cfg.head_dim * 2
    assert weight_sized_moves(compiled.as_text(), matrix) == []
    # The entry's copies were the tick's temporaries: 0.4034 GB before.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01 * GB


# -- the shared-K/V family's programs (ISSUE 35) --------------------------------

# program: (temporaries allowed in GB, ``while``s nested, chunk-scan
# kernels in the text).  The tick at the slot's whole span gathers the one
# cached layer's window 8 times (16 slots x 5120 positions x 1280 x 2 B =
# 0.21 GB for K and as much for V, alive one reader at a time): 0.56 GB
# compiled (PR 35); the last-chunk program's full layer holds its scores,
# 0.48 GB.  The kernel is in the chunk program twice — the scanned MW
# segment's body and layer 16 inline — and never in the tick.
SHARED_KV_PROGRAMS = {
    ("decode", 5120): (0.7, 2, 0),
    ("chunk", 256, 5120): (0.7, 1, 2),
    # The lane's 2048 rung (ISSUE 42): the same loops, a narrower gather.
    ("chunk", 256, 2048): (0.7, 1, 2),
}


@pytest.mark.parametrize("program", list(SHARED_KV_PROGRAMS))
def test_shared_kv_programs_nest_their_loops_and_stay_small(
        one_chip, as_on_tpu, monkeypatch, program):
    """Phi-4-mini-flash-reasoning's own tick and last-chunk program at
    their real sizes: the tick nests two ``while``s (steps, a segment's
    repeats) and every chunk program one, which is how the benchmark files
    them; the chunk's recurrence is the kernel, not an unrolled loop."""
    temp_limit_gb, depth, kernels = SHARED_KV_PROGRAMS[program]
    tier = _bench_tier(monkeypatch, "phi-4-mini-flash-reasoning")
    engine, pool, compiled, _ = _pool_program(one_chip, tier, program)
    assert engine.cfg.shared_kv and engine.ragged is False
    assert pool["k"].shape == (1, 1281, 64, 1280)
    assert pool["rk"].shape == (8, 16, 512, 1280)
    assert pool["s"].shape == (9, 16, 16, 5120)
    from test_latent_moe import _while_depth
    text = compiled.as_text()
    assert _while_depth(text) == depth
    # The chunk program's loops: the segment that repeats before the
    # cached layer and — under the conditional only a prompt's last chunk
    # takes — the one after it.  By that second loop the benchmark tells
    # a self-only chunk from a full-depth one in the device trace
    # (benchmark/layer_metrics/shared_kv_readers.py); the tick runs both
    # inside its loop over the steps.
    chunk = program[0] == "chunk"
    assert text.count(" while(") == (2 if chunk else 3)
    assert (" conditional(" in text) is chunk
    assert text.count("tpu_custom_call") == kernels
    assert ("ssm_chunk_scan" in text) is bool(kernels)
    temp_gb = compiled.memory_analysis().temp_size_in_bytes / GB
    assert temp_gb < temp_limit_gb, temp_gb


# -- the named scope of every operation (ISSUE 56) ----------------------------

SCOPED_PROGRAMS = {"dense tick": ("nano_test", ("decode", 64)),
                   "dense chunk": ("nano_test", ("chunk", 16, 64)),
                   "hybrid tick": ("hybrid_test", ("decode", 64)),
                   "hybrid chunk": ("hybrid_test", ("chunk", 16, 64))}
# The share of a program's instructions that may rest under no scope: the
# tick's and the loops' own counters and conditions, the buffers the
# compiler allocates.  Before ISSUE 56 the four read 67, 69, 53 and 52 %.
MAX_UNSCOPED_SHARE = 0.10


@pytest.mark.parametrize("case", list(SCOPED_PROGRAMS))
def test_few_instructions_rest_under_no_scope(one_chip, as_on_tpu, case):
    """What the chip's compiler makes of a dense and a hybrid tiny
    preset's tick and chunk program: every instruction a trace can show
    has a named scope but for a stated share, the head has one, and a
    fusion across scopes that do not nest is the exception."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.obs.program_scopes import op_scopes
    preset, program = SCOPED_PROGRAMS[case]
    tier = TierConfig(name="nano", model_preset=preset, decode_batch=4,
                      kv_block_size=16, prefill_buckets=(16, 32, 64, 128),
                      prefill_chunk_tokens=16, enable_prefix_cache=False)
    try:
        _, _, compiled, _ = _compile_pool_program(one_chip, tier, program)
    finally:
        # The tiny presets' shapes are the CPU tests' own: what was traced
        # here for the chip (a kernel not interpreted) must not be found
        # by a later test of this process.
        jax.clear_caches()
    ops = op_scopes(compiled.as_text())
    unscoped = sorted(k for k, v in ops.items() if v["scope"] is None)
    assert len(ops) > 100
    assert len(unscoped) < MAX_UNSCOPED_SHARE * len(ops), unscoped
    scopes = {v["scope"] for v in ops.values()}
    assert {"head", "embed", "sample", "mixer_proj", "layer_scan",
            "attention", "kv_write"} <= scopes
    if program[0] == "decode":
        assert "step_scan" in scopes
    mixed = sorted(k for k, v in ops.items() if v["mixed"])
    assert len(mixed) < 0.10 * len(ops), mixed
