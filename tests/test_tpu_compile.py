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
import os
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

KERNELS = ["flash_causal_attention", "flash_decode_attention",
           "flash_chunk_attention", "paged_decode_attention",
           "ragged_paged_decode_attention",
           "ragged_paged_decode_attention_q8",
           "ragged_paged_verify_attention"]


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
    from distributed_llm_tpu.ops import pallas_attention, ragged_attention
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    monkeypatch.setattr(ragged_attention, "_interpret", lambda: False)


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


def _nano_tick(one_chip):
    """The batched engine's own decode-tick program at nano_1b and full
    KV residency, built the way the chip builds it, lowered on shapes
    (jax.eval_shape params — nothing of the 1B model materializes)."""
    from distributed_llm_tpu import models
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    from distributed_llm_tpu.engine.paged_kv import PagedConfig, init_pool

    tier = flagship_cluster(n_devices=1).nano
    cfg = tier.model()
    params = _on(one_chip, jax.eval_shape(
        partial(models.init_params, cfg, seed=0)))
    # The engine's own pool stays tiny (it is real host memory); the
    # program is lowered against the pool shape under test.
    engine = ContinuousBatchingEngine(
        dataclasses.replace(tier, kv_pool_blocks=40), params=params)
    paged = PagedConfig(block_size=tier.kv_block_size,
                        max_slots=tier.decode_batch,
                        max_seq_len=cfg.max_seq_len)
    pool = _on(one_chip, jax.eval_shape(
        lambda: init_pool(cfg, paged, tier.kv_quantize)))
    b = tier.decode_batch
    wb = (paged.blocks_per_slot if engine.ragged
          else engine._buckets[0] // tier.kv_block_size)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    try:
        compiled = engine._decode_step().lower(
            params, pool, arg((b, wb)), arg((b,)), arg((b,)),
            arg((b,), jnp.float32), arg((2,), jnp.uint32)).compile()
    finally:
        engine.stop()
    return engine, compiled


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The engine asks jax.default_backend() which path to take; here
    the answer is steered to the chip's (and the dispatch table, which
    only steers its own backend, is re-read under it)."""
    from distributed_llm_tpu.ops import attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "_DISPATCH_TABLE", None)
    monkeypatch.setattr(attention, "_DISPATCH_META", None)
    for var in ("DLLM_RAGGED", "DLLM_ATTENTION"):
        monkeypatch.delenv(var, raising=False)


def test_default_nano_tick_on_tpu_is_dense_windowed_xla(one_chip, as_on_tpu):
    """What the committed dispatch table makes of the chip's default
    path: the dense windowed tick with the XLA gather — no kernel in the
    program (chip_smoke.py's what-ran lines say the same of the attached
    chip)."""
    engine, compiled = _nano_tick(one_chip)
    assert engine.ragged is False and engine.spec is False
    assert engine.cfg.attention_impl == "pallas"
    assert "tpu_custom_call" not in compiled.as_text()


def test_ragged_pallas_nano_tick_compiles_for_v5e(one_chip, as_on_tpu,
                                                  monkeypatch):
    """The tick PRs 6 and 14 built — fused ragged decode on the Pallas
    kernel — which a re-measured dispatch row would switch on: forced
    here, it lowers with the kernel inside."""
    monkeypatch.setenv("DLLM_RAGGED", "1")
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    engine, compiled = _nano_tick(one_chip)
    assert engine.ragged is True
    assert compiled.as_text().count("tpu_custom_call") == 1
