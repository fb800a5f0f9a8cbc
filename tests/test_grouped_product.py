"""The routed experts' grouped product (ops/grouped_product.py), on the
CPU in interpreter mode (``ops.pallas_attention._interpret``): against
``jax.lax.ragged_dot`` and against a plain per-row ``x[i] @ w[g[i]]``,
and the static test that chooses between kernel and ``ragged_dot`` inside
``latent_moe._grouped``.  tests/test_tpu_compile.py compiles the kernel
for a described v5e at the cells' published widths; chip_smoke.py runs it
on the chip.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
from distributed_llm_tpu.models import latent_moe
from distributed_llm_tpu.ops import grouped_product as GP
from distributed_llm_tpu.ops import quant


def _sizes(rng, groups: int, first: int, span: int, rows_in: int,
           touched: int) -> np.ndarray:
    """``rows_in`` rows over ``touched`` of the groups ``first`` ..
    ``first + span``, at least one each; every other group empty."""
    sizes = np.zeros(groups, np.int32)
    ids = first + rng.choice(span, touched, replace=False)
    sizes[ids] = 1
    for g in rng.choice(ids, rows_in - touched):
        sizes[g] += 1
    return sizes


# name: (rows, layers, groups a layer, layer with the rows, rows in groups,
#        groups touched, in, out, kernel options).  The tick shapes keep
# the awkward factors of the published widths at a fraction of the size:
# 2688 is 21 lane-widths, 384 and 896 are 3 and 7 (no multiple of 256),
# 464 = 1856 / 4 is 3.625 lane-widths as an ``in``.
CASES = {
    "wide-reasoning-tick-up": (96, 2, 64, 1, 48, 26, 2688, 384, {}),
    "wide-reasoning-tick-down": (96, 2, 64, 1, 48, 26, 384, 2688, {}),
    "wide-reasoning-tick-in-464": (96, 2, 64, 0, 51, 30, 464, 640, {}),
    "reasoned-reply-tick-up": (32, 5, 64, 2, 32, 23, 896, 256, {}),
    "reasoned-reply-tick-down": (32, 5, 64, 2, 32, 23, 256, 896, {}),
    "stacked-first-layer": (32, 5, 64, 0, 32, 20, 256, 128, {}),
    "stacked-last-layer": (32, 5, 64, 4, 32, 20, 256, 128, {}),
    "every-row-in-one-group": (96, 2, 64, 1, 96, 1, 256, 384, {}),
    "every-row-in-the-last-group": (32, 1, 8, 0, 32, 1, 128, 128,
                                    {"last": True}),
    "no-row-in-any-group": (32, 2, 8, 0, 0, 0, 128, 128, {}),
    "trailing-rows-in-no-group": (96, 2, 64, 0, 7, 5, 256, 256, {}),
    "one-row-in-all": (1, 2, 8, 1, 1, 1, 256, 128, {}),
    "rows-not-a-sublane-tile": (23, 1, 16, 0, 23, 9, 128, 256, {}),
    # Past ROW_BLOCK rows a group's rows are blocks that start at any
    # sublane tile: groups of many blocks, of one, and a matrix read in
    # several DMAs and multiplied in several pieces.
    "chunk-many-rows-a-group": (300, 2, 16, 1, 280, 6, 256, 2304,
                                {"tile_bytes": 64 << 10}),
    "chunk-few-rows-a-group": (1536, 2, 64, 1, 768, 56, 128, 256, {}),
    "float32-rows": (32, 2, 8, 1, 30, 6, 128, 128, {"dtype": jnp.float32}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_the_grouped_product(case):
    (rows, layers, per, layer, rows_in, touched, k, n, opts) = CASES[case]
    opts = dict(opts)
    dtype = opts.pop("dtype", jnp.bfloat16)
    groups = layers * per
    rng = np.random.default_rng(len(case))
    if opts.pop("last", False):
        sizes = np.zeros(groups, np.int32)
        sizes[-1] = rows_in
    else:
        sizes = _sizes(rng, groups, layer * per, per, rows_in, touched)
    assert sizes.sum() == rows_in and np.count_nonzero(sizes) == touched
    x = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    w = jnp.asarray(rng.standard_normal((groups, k, n)) * k ** -0.5, dtype)
    assert GP.serves(rows, groups, k, n, dtype)

    got = GP.grouped_product(x, w, jnp.asarray(sizes), **opts)
    assert got.shape == (rows, n) and got.dtype == dtype
    got = np.asarray(got, np.float32)

    group_of_row = np.repeat(np.arange(groups), sizes)
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    plain = np.zeros((rows, n), np.float32)
    for i, g in enumerate(group_of_row):
        plain[i] = xf[i] @ wf[g]
    ragged = np.asarray(jax.lax.ragged_dot(x, w, jnp.asarray(sizes)),
                        np.float32)
    # Dropless: every row of a non-empty group, to the rounding of the
    # result's dtype (float32 accumulation in both).
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got[:rows_in], plain[:rows_in], atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(got[:rows_in], ragged[:rows_in], atol=tol,
                               rtol=tol)
    # Rows that belong to no group: zeros, a defined value.
    assert not got[rows_in:].any()


def test_the_tiles_follow_the_matrix():
    """DMAs of whole sublane tiles of rows, a few MB each at most, the
    last one ending with the matrix; nothing of 2816 x 2048 baked in."""
    for k, n in ((2816, 2048), (2048, 2816), (3584, 1024), (1024, 3584),
                 (2688, 1920), (1920, 2688), (1856, 2688), (48, 128)):
        tiles = GP.dma_tiles(k, n, 2)
        assert tiles[0][0] == 0 and sum(h for _, h in tiles) == k
        assert all(a + h == b for (a, h), (b, _) in zip(tiles, tiles[1:]))
        assert all(h % GP.ROW_ALIGN == 0 and h * n * 2 <= GP.TILE_BYTES
                   for _, h in tiles)
    assert GP._split(1920, 1024, 128) == [(0, 1024), (1024, 896)]
    assert GP.dma_tiles(2688, 1920, 2) == [(0, 1344), (1344, 1344)]
    with pytest.raises(ValueError, match="whole tiles"):
        GP.grouped_product(jnp.zeros((8, 200), jnp.bfloat16),
                           jnp.zeros((4, 200, 128), jnp.bfloat16),
                           jnp.zeros(4, jnp.int32))


# -- an expert layer's whole FFN as one call (ISSUE 53) ------------------------

# name: (rows, layers, groups a layer, layer with the rows, rows in groups,
#        groups touched, in, F, out, gated, options).  The cells'
# ratios at smaller widths: 2688 / 1920 at a third (7 and 5 lane-widths;
# 2816 / 2048, what that cell stored until ISSUE 55, at a half: 11 and 8)
# and 3584 / 1024 at a quarter (7 and 2); ``out`` is ``in`` in the models,
# and another width in three cases so that nothing leans on that.
FFN_CASES = {
    "context-reasoning-tick-gated": (16, 20, 16, 10, 16, 7, 256, 256, 256,
                                     True, {}),
    "wide-reasoning-tick-relu2": (96, 2, 32, 1, 48, 20, 896, 640, 896,
                                  False, {}),
    "stored-by-256-tick-relu2": (96, 2, 32, 1, 48, 20, 1408, 1024, 1408,
                                 False, {}),
    "reasoned-reply-tick-gated": (32, 5, 64, 2, 32, 23, 896, 256, 896,
                                  True, {}),
    "stacked-first-layer-gated": (32, 5, 16, 0, 32, 9, 128, 128, 128, True,
                                  {}),
    "stacked-last-layer-relu2": (32, 5, 16, 4, 32, 9, 128, 128, 128, False,
                                 {}),
    "every-row-in-the-last-group": (32, 2, 8, 0, 32, 1, 128, 128, 128,
                                    True, {"last": True}),
    "no-row-in-any-group": (32, 2, 8, 0, 0, 0, 128, 128, 128, True, {}),
    "trailing-rows-in-no-group-gated": (96, 2, 64, 0, 7, 5, 128, 256, 128,
                                        True, {}),
    "trailing-rows-in-no-group-relu2": (96, 2, 64, 0, 7, 5, 128, 256, 128,
                                        False, {}),
    "rows-not-a-sublane-tile": (23, 1, 16, 0, 23, 9, 128, 256, 128, True,
                                {}),
    "one-row-in-all": (1, 2, 8, 1, 1, 1, 256, 128, 256, False, {}),
    "chunk-many-rows-a-group-gated": (300, 2, 16, 1, 280, 6, 256, 1152, 384,
                                      True, {"tile_bytes": 64 << 10}),
    "chunk-many-rows-a-group-relu2": (300, 2, 16, 1, 280, 6, 256, 1152, 384,
                                      False, {"tile_bytes": 64 << 10}),
    "chunk-few-rows-a-group-gated": (1536, 2, 64, 1, 768, 56, 128, 256, 128,
                                     True, {}),
    "chunk-few-rows-a-group-relu2": (1536, 2, 64, 1, 768, 56, 128, 256, 128,
                                     False, {}),
    "out-wider-than-in": (48, 2, 16, 1, 40, 11, 128, 384, 640, True, {}),
    "float32-gated": (32, 2, 8, 1, 30, 6, 128, 128, 128, True,
                      {"dtype": jnp.float32}),
    "float32-relu2": (32, 2, 8, 1, 30, 6, 128, 256, 128, False,
                      {"dtype": jnp.float32}),
}


def _chain(x, gate, up, down, sizes, **opts):
    """The experts' FFN as three (two) calls of the grouped product, the
    activation between them in float32 and rounded once, as the TPU's
    compiler takes what the models wrote before ISSUE 53."""
    f32 = jnp.float32
    a = GP.grouped_product(x, up, sizes, **opts).astype(f32)
    if gate is None:
        a = jnp.square(jax.nn.relu(a))
    else:
        a = jax.nn.silu(GP.grouped_product(x, gate, sizes, **opts
                                           ).astype(f32)) * a
    return GP.grouped_product(a.astype(x.dtype), down, sizes, **opts)


@pytest.mark.parametrize("case", list(FFN_CASES))
def test_fused_ffn_is_the_chain_of_products_bit_for_bit(case):
    (rows, layers, per, layer, rows_in, touched, k, f, n, gated,
     opts) = FFN_CASES[case]
    opts = dict(opts)
    dtype = opts.pop("dtype", jnp.bfloat16)
    groups = layers * per
    rng = np.random.default_rng(len(case))
    if opts.pop("last", False):
        sizes = np.zeros(groups, np.int32)
        sizes[-1] = rows_in
    else:
        sizes = _sizes(rng, groups, layer * per, per, rows_in, touched)
    assert sizes.sum() == rows_in and np.count_nonzero(sizes) == touched

    def matrix(*shape):
        return jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5,
                           dtype)
    x = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    gate = matrix(groups, k, f) if gated else None
    up, down = matrix(groups, k, f), matrix(groups, f, n)
    sizes = jnp.asarray(sizes)
    assert GP.serves_ffn(rows, groups, k, f, n, dtype, gated)

    got = GP.grouped_ffn(x, gate, up, down, sizes, **opts)
    assert got.shape == (rows, n) and got.dtype == dtype
    want = jax.jit(functools.partial(_chain, **opts))(x, gate, up, down,
                                                      sizes)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # The same roundings at the same places: not a bit apart.
    assert np.array_equal(got, want), np.abs(got - want).max()
    assert rows_in == 0 or np.abs(got[:rows_in]).max() > 0.1
    assert not got[rows_in:].any()


def test_fused_ffn_refuses_matrices_off_the_tiles():
    x = jnp.zeros((8, 128), jnp.bfloat16)
    sizes = jnp.zeros(4, jnp.int32)
    with pytest.raises(ValueError, match="whole tiles"):
        GP.grouped_ffn(x, None, jnp.zeros((4, 128, 200), jnp.bfloat16),
                       jnp.zeros((4, 200, 128), jnp.bfloat16), sizes)


W = jax.ShapeDtypeStruct


# What ``_grouped`` traces, by shapes alone: (rows, w, implementation).
BRANCHES = {
    "wide-reasoning-tick": (96, W((128, 2688, 1920), jnp.bfloat16),
                            "pallas"),
    "wide-reasoning-tick-down": (96, W((128, 1920, 2688), jnp.bfloat16),
                                 "pallas"),
    "reasoned-reply-tick": (32, W((320, 3584, 1024), jnp.bfloat16),
                            "pallas"),
    "reasoned-reply-tick-down": (32, W((320, 1024, 3584), jnp.bfloat16),
                                 "pallas"),
    # What that cell stored until ISSUE 55 (multiples of 256).
    "stored-by-256": (96, W((128, 2816, 2048), jnp.bfloat16), "pallas"),
    "stored-by-256-down": (96, W((128, 2048, 2816), jnp.bfloat16),
                           "pallas"),
    # A minor width off the lanes: the chip's compiler cannot cut such a
    # matrix out of the stack by index (and the device rests it
    # transposed: models/hybrid_ssm.py ``expert_dims_stored``).
    "out-not-whole-lanes": (96, W((128, 2688, 1856), jnp.bfloat16),
                            "ragged_dot"),
    "in-not-whole-sublane-tiles": (8, W((8, 200, 128), jnp.bfloat16),
                                   "ragged_dot"),
    "many-rows-a-group": (GP.MAX_ROWS_A_GROUP * 8 + 1,
                          W((8, 128, 128), jnp.bfloat16), "ragged_dot"),
    "more-than-vmem-holds": (8192, W((320, 3584, 1024), jnp.bfloat16),
                             "ragged_dot"),
    "matrices-larger-than-the-buffer": (8, W((8, 8192, 4096), jnp.bfloat16),
                                        "ragged_dot"),
    "int8-weights": (32, "int8", "ragged_dot"),
    "int-rows": (32, W((8, 128, 128), jnp.int8), "ragged_dot"),
}


@pytest.mark.parametrize("case", list(BRANCHES))
def test_grouped_chooses_by_static_shapes(case, monkeypatch):
    rows, w, impl = BRANCHES[case]
    if isinstance(w, str):
        w = quant.quantize_tensor(jnp.asarray(
            np.random.default_rng(0).standard_normal((8, 128, 128)),
            jnp.bfloat16))
        assert quant.is_quantized(w)
    assert latent_moe.grouped_impl(rows, w) == impl
    if isinstance(w, jax.ShapeDtypeStruct) and w.shape[0] * w.shape[1] \
            * w.shape[2] > 1 << 22:
        return                      # the choice alone: too large to run here

    # And ``_grouped`` goes where ``grouped_impl`` says.
    called = []
    real = GP.grouped_product
    monkeypatch.setattr(
        GP, "grouped_product",
        lambda *a, **kw: called.append("pallas") or real(*a, **kw))
    if isinstance(w, jax.ShapeDtypeStruct):
        if not jnp.issubdtype(w.dtype, jnp.floating):
            return                  # ragged_dot has no int8 rows either
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal(w.shape), w.dtype)
        groups, k, n = w.shape
        dense = np.asarray(w, np.float32)
    else:
        groups, k, n = w["q"].shape
        dense = np.asarray(quant.dequantize(w), np.float32)
    sizes = np.zeros(groups, np.int32)
    sizes[[1, groups - 1]] = (rows // 2, rows - rows // 2)
    group_of_row = jnp.asarray(np.repeat(np.arange(groups), sizes))
    x = jnp.asarray(np.random.default_rng(1).standard_normal((rows, k)),
                    jnp.bfloat16)
    got = latent_moe._grouped(x, w, jnp.asarray(sizes), group_of_row)
    assert called == (["pallas"] if impl == "pallas" else [])
    want = np.einsum("rk,rkn->rn", np.asarray(x, np.float32),
                     dense[np.asarray(group_of_row)])
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=0.1, rtol=5e-2)


# What ``expert_ffn`` traces, by shapes alone: (rows, gate, up, down,
# form).  Every shape under "pallas_ffn" is one a benchmark cell runs,
# or ran.
FFN_BRANCHES = {
    "context-reasoning-tick": (16, True, W((320, 2048, 2048), jnp.bfloat16),
                               W((320, 2048, 2048), jnp.bfloat16),
                               "pallas_ffn"),
    "context-reasoning-chunk": (256, True,
                                W((320, 2048, 2048), jnp.bfloat16),
                                W((320, 2048, 2048), jnp.bfloat16),
                                "pallas_ffn"),
    "wide-reasoning-tick": (96, False, W((128, 2688, 1920), jnp.bfloat16),
                            W((128, 1920, 2688), jnp.bfloat16),
                            "pallas_ffn"),
    "wide-reasoning-chunk": (1536, False,
                             W((128, 2688, 1920), jnp.bfloat16),
                             W((128, 1920, 2688), jnp.bfloat16),
                             "pallas_ffn"),
    # What that cell stored until ISSUE 55 (multiples of 256).
    "stored-by-256-tick": (96, False, W((128, 2816, 2048), jnp.bfloat16),
                           W((128, 2048, 2816), jnp.bfloat16),
                           "pallas_ffn"),
    "stored-by-256-chunk": (1536, False,
                            W((128, 2816, 2048), jnp.bfloat16),
                            W((128, 2048, 2816), jnp.bfloat16),
                            "pallas_ffn"),
    "reasoned-reply-tick": (32, True, W((320, 3584, 1024), jnp.bfloat16),
                            W((320, 1024, 3584), jnp.bfloat16),
                            "pallas_ffn"),
    "reasoned-reply-chunk": (1024, True,
                             W((320, 3584, 1024), jnp.bfloat16),
                             W((320, 1024, 3584), jnp.bfloat16),
                             "pallas_ffn"),
    "small-and-gated": (12, True, W((8, 128, 256), jnp.bfloat16),
                        W((8, 256, 128), jnp.bfloat16), "pallas_ffn"),
    # Each product fits a call of its own; the three (two) matrices, x, h
    # and the result of ONE call do not: the chain of calls.
    "matrices-too-large-for-one-call": (8, True,
                                        W((8, 4096, 4096), jnp.bfloat16),
                                        W((8, 4096, 4096), jnp.bfloat16),
                                        "pallas"),
    "a-chunk-too-wide-for-one-call": (5000, False,
                                      W((320, 3584, 1024), jnp.bfloat16),
                                      W((320, 1024, 3584), jnp.bfloat16),
                                      "pallas"),
    "many-rows-a-group": (GP.MAX_ROWS_A_GROUP * 8 + 1, True,
                          W((8, 128, 128), jnp.bfloat16),
                          W((8, 128, 128), jnp.bfloat16), "ragged_dot"),
    "out-not-whole-lanes": (96, False, W((128, 2688, 1856), jnp.bfloat16),
                            W((128, 1856, 2688), jnp.bfloat16),
                            "pallas+ragged_dot"),
    "int8-experts": (12, True, "int8", "int8", "ragged_dot"),
}


@pytest.mark.parametrize("case", list(FFN_BRANCHES))
def test_expert_ffn_chooses_by_static_shapes(case, monkeypatch):
    rows, gated, up, down, form = FFN_BRANCHES[case]
    rng = np.random.default_rng(0)
    if up == "int8":
        def quantized():
            return quant.quantize_tensor(jnp.asarray(
                rng.standard_normal((8, 128, 128)) * 0.1, jnp.bfloat16))
        gate, up, down = quantized(), quantized(), quantized()
        shape, n = (8, 128, 128), 128
    else:
        gate, shape, n = (up if gated else None), up.shape, down.shape[2]
    assert latent_moe.ffn_impl(rows, gate, up, down) == form
    assert (form == "pallas_ffn") == (
        not quant.is_quantized(up)
        and GP.serves_ffn(rows, *shape, n, up.dtype, gated))
    if shape[0] * shape[1] * shape[2] > 1 << 22 or "+" in form:
        return                      # the choice alone: too large to run here

    # And ``expert_ffn`` goes where ``ffn_impl`` says, to the same numbers.
    called = []
    for name in ("grouped_ffn", "grouped_product"):
        monkeypatch.setattr(
            GP, name, lambda *a, _real=getattr(GP, name), _name=name, **kw:
            called.append(_name) or _real(*a, **kw))

    def dense(w):
        return np.asarray(quant.dequantize(w) if quant.is_quantized(w)
                          else w, np.float32)
    if not quant.is_quantized(up):
        gate, up, down = (
            None if w is None else
            jnp.asarray(rng.standard_normal(w.shape) * 0.1, w.dtype)
            for w in (gate, up, down))
    groups = shape[0]
    sizes = np.zeros(groups, np.int32)
    sizes[[1, groups - 1]] = (rows // 2, rows - rows // 2)
    group_of_row = np.repeat(np.arange(groups), sizes)
    x = jnp.asarray(rng.standard_normal((rows, shape[1])), jnp.bfloat16)
    got = latent_moe.expert_ffn(x, gate, up, down, jnp.asarray(sizes),
                                jnp.asarray(group_of_row))
    assert called == {"pallas_ffn": ["grouped_ffn"],
                      "pallas": ["grouped_product"] * (2 + gated),
                      "ragged_dot": []}[form]
    xf = np.asarray(x, np.float32)
    a = np.einsum("rk,rkn->rn", xf, dense(up)[group_of_row])
    if gated:
        g = np.einsum("rk,rkn->rn", xf, dense(gate)[group_of_row])
        a = g / (1 + np.exp(-g)) * a
    else:
        a = np.maximum(a, 0) ** 2
    want = np.einsum("rk,rkn->rn", a, dense(down)[group_of_row])
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=0.05, rtol=5e-2)


def test_latent_experts_agree_through_either_product(monkeypatch):
    """The latent family's routed experts at widths of whole lanes run
    the ONE fused call; the same call with the static tests turned down
    runs the chain of kernel calls (not a bit apart: the same roundings),
    then ``ragged_dot``: the same experts chosen, the same sum to
    bfloat16's rounding.  (``latent_test`` itself is 64 x 32:
    ``ragged_dot``.)"""
    cfg = dataclasses.replace(MODEL_PRESETS["latent_test"], hidden_size=128,
                              moe_ffn_size=128)
    params = latent_moe.init_params(cfg, seed=3)
    layers = params["layers"]
    stacked = {key: layers[key] for key in latent_moe.EXPERT_KEYS}
    lp = jax.tree.map(lambda a: a[1], layers)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (8, cfg.hidden_size)), jnp.float32)

    def through(form):
        assert latent_moe.grouped_product_form(
            cfg, latent_moe.expert_stacks(params), 8) == form
        out, counts = latent_moe.routed_experts(cfg, lp, x, stacked, 1)
        return np.asarray(out, np.float32), np.asarray(counts)
    out_f, counts_f = through("pallas_ffn")
    monkeypatch.setattr(GP, "serves_ffn", lambda *a: False)
    out_k, counts_k = through("pallas")
    monkeypatch.setattr(GP, "serves", lambda *a: False)
    out_r, counts_r = through("ragged_dot")
    assert np.abs(out_f).max() > 0.01
    assert np.array_equal(counts_f, counts_k)
    assert np.array_equal(counts_k, counts_r)
    assert np.array_equal(out_f, out_k)
    np.testing.assert_allclose(out_k, out_r, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("preset", ["hybrid_test", "hybrid_cca_test"])
def test_hybrid_experts_agree_through_either_product(preset, monkeypatch):
    """The hybrid family's twin, under both routers: the sigmoid router
    over 8 outputs of which 4 are held (``relu2``; the absent experts'
    rows masked, the pad to the stored width and its cut outside the
    call) and the MLP router with its carry (top-1 of 4 gated experts)."""
    from distributed_llm_tpu.models import hybrid_ssm
    cfg = MODEL_PRESETS[preset]
    params = hybrid_ssm.init_params(cfg, seed=3)
    at = next(i for i, lp in enumerate(params["periods"])
              if hybrid_ssm.EXPERT_KEYS[-1] in lp)
    stacked = {key: params["periods"][at][key]
               for key in hybrid_ssm.EXPERT_KEYS
               if key in params["periods"][at]}
    assert (len(stacked) == 3) == (cfg.expert_act == "swiglu")
    lp = jax.tree.map(lambda a: a[1], params["periods"][at])
    rng = np.random.default_rng(2)
    tokens = 12
    x = jnp.asarray(rng.standard_normal((tokens, cfg.hidden_size)),
                    jnp.float32)
    carry = jnp.asarray(rng.standard_normal(
        (tokens, max(cfg.router_hidden, 1))), jnp.float32)

    def through(form):
        assert latent_moe.grouped_product_form(
            cfg, hybrid_ssm.expert_stacks(params), tokens) == form
        out, counts, state = hybrid_ssm.routed_experts(cfg, lp, x, stacked,
                                                       1, carry)
        return (np.asarray(out, np.float32), np.asarray(counts),
                np.asarray(state))
    out_f, counts_f, state_f = through("pallas_ffn")
    monkeypatch.setattr(GP, "serves_ffn", lambda *a: False)
    out_k, counts_k, state_k = through("pallas")
    monkeypatch.setattr(GP, "serves", lambda *a: False)
    out_r, counts_r, state_r = through("ragged_dot")
    scale = np.abs(out_r).max()
    assert scale > 1e-3
    # Some assignment went to an expert that is not held where half are.
    assert (counts_f[-1] > 0) == (cfg.experts_held < cfg.num_experts)
    assert counts_f[:-1].sum() + counts_f[-1] == \
        tokens * cfg.experts_per_token
    assert np.array_equal(counts_f, counts_k)
    assert np.array_equal(counts_k, counts_r)
    assert np.array_equal(state_f, state_k)
    assert np.array_equal(out_f, out_k)
    np.testing.assert_allclose(out_k, out_r, atol=2e-2 * scale, rtol=2e-2)


@pytest.mark.parametrize("tokens", [16, 256], ids=["tick", "chunk"])
def test_wide_reasonings_own_stacks_take_the_one_call(tokens):
    """``nemotron-3-nano-30b-a3b``'s experts as ``expert_dims_stored``
    rests them (2688 x 1856 published, top-6, no gate; two periods of 64
    held experts), as shapes alone: the tick's 16 tokens and the chunk
    program's 256 both trace the one kernel call, so a rule that stored a
    width the kernel refuses would fall to ``ragged_dot`` HERE and not
    unseen on the chip (112 GB/s at these widths: PERF.md section 6,
    PR 33)."""
    from distributed_llm_tpu.models import hybrid_ssm
    cfg = dataclasses.replace(MODEL_PRESETS["hybrid_test"], hidden_size=2688,
                              moe_ffn_size=1856, experts_per_token=6)
    h, f = hybrid_ssm.expert_dims_stored(cfg)
    assert (h, f) == (2688, 1920)
    stacks = [W((2, 64, h, f), jnp.bfloat16), W((2, 64, f, h), jnp.bfloat16)]
    assert latent_moe.grouped_product_form(cfg, stacks, tokens) == \
        "pallas_ffn"


@pytest.mark.parametrize("preset,form", [
    # Stored 128 x 128 (``expert_dims_stored``: whole lane-widths): 12
    # and 48 rows over 8 stacked groups: one fused call a layer.
    ("hybrid_test", {"decode": "pallas_ffn", "prefill": "pallas_ffn"}),
    # The gated experts under the MLP router: 2 and 16 rows over 12.
    ("hybrid_cca_test", {"decode": "pallas_ffn", "prefill": "pallas_ffn"}),
    # 64 x 32: widths off the lanes.
    ("latent_test", {"decode": "ragged_dot", "prefill": "ragged_dot"}),
])
def test_stats_name_the_implementation_a_program_was_traced_with(
        preset, form, monkeypatch):
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    tier = TierConfig(name="nano", model_preset=preset, decode_batch=4,
                      kv_block_size=16, prefill_buckets=(16, 32, 64, 128),
                      prefill_chunk_tokens=16, enable_prefix_cache=False)
    engine = ContinuousBatchingEngine(tier)
    try:
        assert engine.grouped_product_form() == form
        assert engine.moe_stats()["grouped_product"] == form
        # Where the one call does not serve, the chain's value.
        monkeypatch.setattr(GP, "serves_ffn", lambda *a: False)
        chain = {stage: value.replace("pallas_ffn", "pallas")
                 for stage, value in form.items()}
        assert engine.grouped_product_form() == chain
    finally:
        engine.stop()
