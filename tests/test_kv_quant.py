"""int8 KV-cache quantization for the paged pool (TierConfig.kv_quantize).

Decode is bandwidth-bound and the KV term overtakes the weight term at
long context × batch; per-row symmetric int8 halves that traffic.  These
tests pin the quantizer's error bound, the paged read/write paths, and
the batched engine end-to-end (including under a TP mesh).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS, tiny_cluster
from distributed_llm_tpu.engine.paged_kv import (PagedConfig,
                                                 dequantize_kv_rows,
                                                 init_pool,
                                                 quantize_kv_rows)


def test_quantize_roundtrip_error_bound():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 16, 64), jnp.bfloat16) * 3.0
    q, scale = quantize_kv_rows(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    back = dequantize_kv_rows(q, scale, jnp.float32)
    # Symmetric per-row int8: error <= scale/2 <= amax/254 per element.
    err = np.abs(np.asarray(back) - np.asarray(x, np.float32))
    amax = np.abs(np.asarray(x, np.float32)).max(axis=-1, keepdims=True)
    assert (err <= amax / 254 + 1e-6).all()
    # Zero rows survive (scale clamps to 1, values to 0).
    q0, s0 = quantize_kv_rows(jnp.zeros((2, 8), jnp.bfloat16))
    assert not np.asarray(q0).any() and (np.asarray(s0) == 1.0).all()


def test_init_pool_int8_layout_and_memory():
    cfg = MODEL_PRESETS["nano_test"]
    pcfg = PagedConfig(block_size=16, max_slots=2, max_seq_len=64)
    pool = init_pool(cfg, pcfg, "int8")
    assert pool["k"].dtype == jnp.int8
    assert pool["ks"].shape == pool["k"].shape[:-1] + (cfg.num_kv_heads,)
    bf16 = init_pool(cfg, pcfg)
    bytes_q = sum(x.size * x.dtype.itemsize for x in pool.values())
    bytes_f = sum(x.size * x.dtype.itemsize for x in bf16.values())
    # Exact: per row, D int8 bytes + one f32 scale vs 2·D bf16 bytes.
    d = cfg.head_dim
    assert bytes_q * (2 * d) == bytes_f * (d + 4)
    with pytest.raises(ValueError):
        init_pool(cfg, pcfg, "int4")


def test_paged_decode_int8_matches_bf16_attention():
    """Op level: the int8 pool's gather+dequant path stays close to the
    bf16 pool on the same values."""
    from distributed_llm_tpu.ops.attention import paged_decode
    key = jax.random.PRNGKey(1)
    nkv, nb, bs, d, nq, b = 2, 5, 16, 32, 4, 2
    kf = jax.random.normal(key, (nkv, nb, bs, d), jnp.bfloat16)
    vf = jax.random.normal(jax.random.PRNGKey(2), (nkv, nb, bs, d),
                           jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(3), (b, nq, d), jnp.bfloat16)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([20, 30], jnp.int32)
    want = paged_decode(q, kf, vf, tables, pos, impl="xla")
    kq, ks = quantize_kv_rows(kf)
    vq, vs = quantize_kv_rows(vf)
    got = paged_decode(q, kq, vq, tables, pos, impl="xla",
                       k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2, rtol=5e-2)


def _tier(**kw):
    return dataclasses.replace(tiny_cluster().nano, decode_batch=2,
                               max_new_tokens=8, **kw)


def test_batched_engine_kv_int8_serves_close_to_bf16():
    """Engine level: an int8-KV engine on trained weights produces the
    same greedy tokens as bf16 for a short generation (quantization noise
    far below the logit margins of a trained model), and its pool really
    is int8."""
    from distributed_llm_tpu.config import default_checkpoint
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    ckpt = default_checkpoint("nano_test")
    if ckpt is None:
        pytest.skip("checkpoints/nano_test not published")
    a = ContinuousBatchingEngine(_tier(checkpoint_path=ckpt), seed=3)
    b = ContinuousBatchingEngine(_tier(checkpoint_path=ckpt,
                                       kv_quantize="int8"), seed=3)
    try:
        pa = a.generate("user: ask the chip about the mesh")
        pb = b.generate("user: ask the chip about the mesh")
        assert b.pool["k"].dtype == jnp.int8
        assert pa.token_ids == pb.token_ids, (pa.text, pb.text)
        # Prefix reuse keeps working over the quantized blocks (prompts
        # kept short enough that turn 2 still fits the largest bucket —
        # tail truncation would legitimately invalidate the prefix).
        h = [{"role": "user", "content": "ask the mesh"}]
        r1 = b.generate(h, max_new_tokens=4)
        h += [{"role": "assistant", "content": r1.text},
              {"role": "user", "content": "and?"}]
        b.generate(h, max_new_tokens=4)
        assert b.prefix_cache.stats()["hits"] >= 1
    finally:
        a.stop()
        b.stop()


def test_tp_mesh_kv_int8_pool_sharded_and_consistent():
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    from distributed_llm_tpu.parallel.mesh import tp_mesh
    tier = dataclasses.replace(tiny_cluster().orin, decode_batch=2,
                               max_new_tokens=6, kv_quantize="int8")
    plain = ContinuousBatchingEngine(tier, seed=21)
    tp = ContinuousBatchingEngine(tier, seed=21,
                                  mesh=tp_mesh(jax.devices(), 4))
    try:
        a = plain.generate("user: int8 pool under tp?").token_ids
        b = tp.generate("user: int8 pool under tp?").token_ids
        assert a == b
        assert tp.pool["ks"].sharding.spec[3] == "tp"
    finally:
        plain.stop()
        tp.stop()


def test_sequential_engine_kv_int8_matches_bf16_tokens():
    """Contiguous-cache int8 (the sequential engine — the headline sweep
    path): same greedy tokens as bf16 on trained weights, int8 cache
    actually in use, and prefix reuse works over quantized parked caches
    (grow + suffix-prefill paths carry the scale planes)."""
    from distributed_llm_tpu.config import default_checkpoint
    from distributed_llm_tpu.engine.inference import InferenceEngine
    ckpt = default_checkpoint("nano_test")
    if ckpt is None:
        pytest.skip("checkpoints/nano_test not published")
    base = dataclasses.replace(tiny_cluster().nano, checkpoint_path=ckpt,
                               max_new_tokens=8)
    a = InferenceEngine(base, seed=3)
    b = InferenceEngine(dataclasses.replace(base, kv_quantize="int8"),
                        seed=3)
    pa = a.generate("user: ask the chip about the mesh")
    pb = b.generate("user: ask the chip about the mesh")
    assert pa.token_ids == pb.token_ids, (pa.text, pb.text)

    h = [{"role": "user", "content": "ask the mesh"}]
    r1 = b.generate(h, max_new_tokens=4)
    h += [{"role": "assistant", "content": r1.text},
          {"role": "user", "content": "and?"}]
    b.generate(h, max_new_tokens=4)
    assert b.prefix_cache.stats()["hits"] >= 1
    # The parked cache really is int8 + scales (LRU list of entries).
    entry = b.prefix_cache._entries[-1]
    assert entry.cache["k"].dtype == jnp.int8
    assert "ks" in entry.cache


def test_sequential_kv_int8_long_prompt_chunked_prefill():
    """The chunk-stride path (prompts past the largest bucket) writes and
    reads the quantized cache correctly: matches bf16 tokens."""
    from distributed_llm_tpu.engine.inference import InferenceEngine
    base = dataclasses.replace(tiny_cluster().nano, max_new_tokens=4,
                               enable_prefix_cache=False)
    long_prompt = "fact about the mesh and the chip. " * 6   # > 64 bucket
    a = InferenceEngine(base, seed=4).generate(long_prompt)
    b = InferenceEngine(dataclasses.replace(base, kv_quantize="int8"),
                        seed=4).generate(long_prompt)
    assert a.token_ids == b.token_ids


def test_moe_tier_kv_int8_falls_back_to_bf16():
    from distributed_llm_tpu.engine.inference import InferenceEngine
    tier = dataclasses.replace(tiny_cluster().nano,
                               model_preset="moe_test",
                               kv_quantize="int8", max_new_tokens=4)
    eng = InferenceEngine(tier, seed=0)
    res = eng.generate("moe int8 gate", max_new_tokens=4)
    assert res.gen_tokens >= 1
    assert eng._kv_quantize == "none"


def test_decode_work_accounts_int8_kv():
    from distributed_llm_tpu.utils import roofline
    cfg = MODEL_PRESETS["nano_test"]
    full = roofline.decode_work(cfg, 4, 64, wbytes=0)
    q8 = roofline.decode_work(cfg, 4, 64, wbytes=0, kv_quantize="int8")
    d = cfg.head_dim
    assert q8["hbm_bytes"] * (2 * d) == pytest.approx(
        full["hbm_bytes"] * (d + 4))
    assert q8["flops"] == full["flops"]


# -- the pool rides the layer loop's carry (ISSUE 27) --------------------------

def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def _step_case(step, kv_quantize):
    """(function of (params, pool), params, a pool with a prefix already
    written) for one of the three paged step functions on nano_test."""
    from distributed_llm_tpu import models
    from distributed_llm_tpu.engine import paged_kv
    cfg = MODEL_PRESETS["nano_test"]
    pcfg = PagedConfig(block_size=16, max_slots=2, max_seq_len=64)
    params = models.init_params(cfg, seed=3)
    pool = init_pool(cfg, pcfg, kv_quantize)
    # Slot 0 owns blocks 1, 2, slot 1 blocks 3, 4; an 18-token prefix is
    # prefilled into slot 0 so the steps attend real K/V across a block
    # boundary.
    tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    prefix = jnp.asarray([[5 + i for i in range(18)] + [0] * 14], jnp.int32)
    _, pool = paged_kv.chunk_prefill_paged(
        cfg, params, prefix, jnp.asarray([0]), jnp.asarray([18]), pool,
        tables[0], 32)
    if step == "decode":
        def fn(params, pool):
            return paged_kv.decode_step_paged(
                cfg, params, jnp.asarray([7, 9]), jnp.asarray([18, 0]), pool,
                tables[:, :2])
    elif step == "chunk":
        def fn(params, pool):
            return paged_kv.chunk_prefill_paged(
                cfg, params, jnp.asarray([[11 + i for i in range(16)]]),
                jnp.asarray([18]), jnp.asarray([30]), pool, tables[0], 48)
    else:
        def fn(params, pool):
            return paged_kv.verify_step_paged(
                cfg, params, jnp.asarray([[7, 8, 9], [4, 5, 6]]),
                jnp.asarray([18, 0]), pool, tables)
    return cfg, fn, params, pool, tables


def _reference_step(step, cfg, params, pool, tables):
    """The straightforward per-layer form the carried loop replaced:
    each layer works on ITS slice of a head-major pool ``[N_kv, NB, bs(,
    D)]`` — head-major row scatter, the attention op on the per-layer
    view — and the slices are stacked back (``lax.scan`` hands them in as ``xs``
    and stacks them as ``ys``; unrolled in Python the same arithmetic
    fuses differently on the CPU and bf16 rounds elsewhere)."""
    from distributed_llm_tpu.models import transformer
    from distributed_llm_tpu.ops import attention, quant
    d, bs = cfg.head_dim, pool["k"].shape[2]
    quantized = "ks" in pool
    # The reference keeps its pool head-major, [L, N_kv, NB, bs(, D)].
    def head_major(x, *heads):
        return jnp.moveaxis(x.reshape(*x.shape[:3], -1, *heads), 3, 1)
    merged = pool
    pool = {key: head_major(x, *([d] if key in "kv" else []))
            for key, x in pool.items()}
    if step == "decode":
        tokens, pos = jnp.asarray([7, 9]), jnp.asarray([18, 0])
        tables = tables[:, :2]
        positions = pos
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], 1)[:, 0]
        lead = (2,)
    elif step == "chunk":
        tokens = jnp.asarray([[11 + i for i in range(16)]])
        start, true_len = jnp.asarray([18]), jnp.asarray([30])
        positions = start[:, None] + jnp.arange(16)[None]
        q_pos = jnp.minimum(positions, true_len[:, None] - 1)
        blk = tables[0][positions[0] // bs]
        lead = (1, 16)
    else:
        tokens, pos = jnp.asarray([[7, 8, 9], [4, 5, 6]]), jnp.asarray([18, 0])
        positions = pos[:, None] + jnp.arange(3)[None]
        blk = jnp.take_along_axis(tables, positions // bs, axis=1)
        lead = (2, 3)
    off = (positions[0] if step == "chunk" else positions) % bs
    x = quant.embed_rows(params["embed"], tokens)
    sin, cos = transformer.rope_sincos(positions, d, cfg.rope_theta)
    def one_layer(x, scanned):
        lp, view = scanned
        view = dict(view)
        h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = quant.matmul(h_in, lp["wq"]).reshape(*lead, cfg.num_heads, d)
        k = quant.matmul(h_in, lp["wk"]).reshape(*lead, cfg.num_kv_heads, d)
        v = quant.matmul(h_in, lp["wv"]).reshape(*lead, cfg.num_kv_heads, d)
        q = transformer.apply_rope(q, sin, cos)
        k = transformer.apply_rope(k, sin, cos)
        rows = {"k": k[0] if step == "chunk" else k,
                "v": v[0] if step == "chunk" else v}
        if quantized:
            rows["k"], rows["ks"] = quantize_kv_rows(rows["k"])
            rows["v"], rows["vs"] = quantize_kv_rows(rows["v"])
        for key, r in rows.items():   # [..., N_kv(, D)] -> head-major
            view[key] = view[key].at[:, blk, off].set(jnp.moveaxis(
                r, blk.ndim, 0))
        scales = dict(k_scale=view.get("ks"), v_scale=view.get("vs"))
        if step == "decode":
            attn = attention.paged_decode(q, view["k"], view["v"], tables,
                                          pos, impl=cfg.attention_impl,
                                          **scales)
        elif step == "chunk":
            attn = attention.paged_chunk(q, view["k"], view["v"], tables[0],
                                         q_pos, 48, **scales)
        else:
            attn = attention.ragged_verify(q, view["k"], view["v"], tables,
                                           pos, **scales)
        x = x + quant.matmul(attn.reshape(*lead, cfg.num_heads * d),
                             lp["wo"])
        h_ffn = transformer.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + transformer._swiglu(h_ffn, lp["w_gate"], lp["w_up"],
                                    lp["w_down"])
        return x, view

    x, new_pool = jax.lax.scan(one_layer, x, (params["layers"], dict(pool)))
    hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    out = (hidden if step == "chunk"
           else transformer.logits_from_hidden(params, hidden))
    # ... and hands it back token-major, heads merged, to be compared.
    return out, {key: jnp.moveaxis(x_, 1, 3).reshape(merged[key].shape)
                 for key, x_ in new_pool.items()}


@pytest.mark.parametrize("kv_quantize", ["none", "int8"])
@pytest.mark.parametrize("step", ["decode", "chunk", "verify"])
def test_step_carries_the_pool_and_matches_a_per_layer_reference(
        step, kv_quantize):
    cfg, fn, params, pool, tables = _step_case(step, kv_quantize)
    # The pool rides the layer scan's CARRY: scanned over as xs (or
    # stacked back as ys) it is sliced and rewritten every step.
    pool_shapes = {x.shape for x in pool.values()}
    scans = list(_scans(jax.make_jaxpr(fn)(params, pool).jaxpr))
    assert scans
    for eqn in scans:
        n_fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
        carried = {v.aval.shape for v in
                   eqn.invars[eqn.params["num_consts"]:n_fixed]}
        moved = ([v.aval.shape for v in eqn.invars[n_fixed:]]
                 + [v.aval.shape for v in
                    eqn.outvars[eqn.params["num_carry"]:]])
        assert pool_shapes <= carried, (carried, pool_shapes)
        assert not pool_shapes & set(moved), moved
    # Same arithmetic, other addresses: logits (hidden states for the
    # chunk program) and every pool array equal to the last bit.
    out, new_pool = jax.jit(fn)(params, pool)
    ref_out, ref_pool = jax.jit(
        lambda params, pool: _reference_step(step, cfg, params, pool,
                                             tables))(params, pool)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref_out, np.float32))
    assert set(new_pool) == set(ref_pool) == set(pool)
    for key in pool:
        assert new_pool[key].dtype == pool[key].dtype
        np.testing.assert_array_equal(
            np.asarray(new_pool[key].astype(jnp.float32)),
            np.asarray(ref_pool[key].astype(jnp.float32)), err_msg=key)
    # ... and the step wrote something (the comparison is not of zeros).
    assert not np.array_equal(
        np.asarray(new_pool["k"].astype(jnp.float32)),
        np.asarray(pool["k"].astype(jnp.float32)))
