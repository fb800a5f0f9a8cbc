"""The attention ops behind every call site (ops/attention.py), each held
to a float64 NumPy attention written here — not to another form of the
code under test.

One parametrised module over what the engines hand the ops: the one paged
decode op, ``ragged_verify`` and ``paged_chunk`` over the WHOLE token-major
pool at ``layer=i`` and over the head-major views a tp hook passes;
``decode`` and ``chunk`` over a contiguous cache; query/K-V head ratios 1,
4, 8 and 20:1 at head 64 and 128; float32, bfloat16 and int8 pools;
shuffled tables, skewed lengths at full occupancy with an idle slot over
the trash block; windows short of the table.  What no slot may read is
poisoned: every block outside the tables, the other layer and the blocks
past a window are NaN, K past a slot's own frontier is NaN, V there is
1e4 (finite: its weight is an exact zero, and 0 x NaN is NaN in any
product; the engine's own unwritten cells are finite too).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.engine import paged_kv
from distributed_llm_tpu.engine.paged_kv import TRASH_BLOCK
from distributed_llm_tpu.ops import attention as A

# (query heads, K/V heads, head_dim): ratios 1, 4, 8 and 20:1 (SmolLM2's
# MHA, nano_1b's and orin_bench's GQA, Jamba2's one K/V head).
HEADS = {"1to1-d64": (4, 4, 64), "4to1-d64": (8, 2, 64),
         "8to1-d128": (8, 1, 128), "20to1-d128": (20, 1, 128)}
POOLS = ("float32", "bfloat16", "int8")
TOL = {"float32": 2e-5, "bfloat16": 2e-2, "int8": 2e-5}
BS, MB, LAYERS, LAYER = 16, 4, 2, 1
V_POISON = 1e4


@pytest.fixture(autouse=True)
def no_override(monkeypatch):
    monkeypatch.delenv("DLLM_ATTENTION", raising=False)


def reference(q, k, v, limit):
    """float64: q [B, G, Nq, D], k/v [B, S, Nkv, D], limit [B, G]; query
    (b, g) attends positions <= limit[b, g] and touches no other."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b_, g_, nq, d = q.shape
    groups = nq // k.shape[2]
    out = np.zeros_like(q)
    for b in range(b_):
        for g in range(g_):
            n = int(limit[b, g]) + 1
            for h in range(nq):
                s = k[b, :n, h // groups] @ q[b, g, h] * d ** -0.5
                p = np.exp(s - s.max())
                out[b, g, h] = (p / p.sum()) @ v[b, :n, h // groups]
    assert np.isfinite(out).all()
    return out


def _stored(x, pool):
    """``x`` as a pool of that kind stores it: (array, scales or None,
    the float64 values it stands for).  int8 by a quantizer of the test's
    own: a scale a (row, head), symmetric."""
    if pool == "int8":
        scale = np.maximum(np.abs(x).max(axis=-1), 1e-6) / 127.0
        rows = np.clip(np.rint(x / scale[..., None]), -127, 127)
        return (rows.astype(np.int8), scale.astype(np.float32),
                rows * scale.astype(np.float32).astype(np.float64)[..., None])
    rows = jnp.asarray(x, jnp.dtype(pool))
    return rows, None, np.asarray(rows.astype(jnp.float32), np.float64)


def _dtype(pool):
    return jnp.float32 if pool == "int8" else jnp.dtype(pool)


def paged_case(heads, pool, pos, q_len, seed=0, window_blocks=MB):
    """One batch over a pool of two layers.  ``pos`` [B]: the first
    query's position a slot, None an idle slot (its table all trash).
    Gives the op's arguments in both representations and the float64
    sequences the reference reads."""
    nq, nkv, d = heads
    rng = np.random.default_rng(seed)
    b = len(pos)
    nb = b * MB + 1
    span = MB * BS
    tables = (rng.permutation(nb - 1) + 1).reshape(b, MB).astype(np.int32)
    k_seq = rng.standard_normal((b, span, nkv, d))
    v_seq = rng.standard_normal((b, span, nkv, d))
    first = np.array([0 if p is None else p for p in pos], np.int32)
    for i, p in enumerate(pos):
        if p is None:
            tables[i] = TRASH_BLOCK
    stored = {}
    for name, seq, poison in (("k", k_seq, np.nan), ("v", v_seq, V_POISON)):
        rows, scales, values = _stored(seq, pool)
        rows, values = np.array(rows), values.copy()
        scales = None if scales is None else scales.copy()
        for i in range(b):
            beyond = first[i] + q_len            # past the slot's frontier
            values[i, beyond:] = np.nan          # the reference reads none
            if scales is None:
                rows[i, beyond:] = poison
            else:                                # int8 rows cannot be NaN
                rows[i, beyond:] = 127
                scales[i, beyond:] = poison
        stored[name] = (rows, scales, values)

    def pooled(rows, fill):
        """[B, S, Nkv(, D)] sequences -> [L, NB, bs, Nkv(, D)]: each
        slot's blocks where its table says, everything else ``fill``."""
        out = np.full((LAYERS, nb, BS) + rows.shape[2:], fill, rows.dtype)
        out[LAYER, 0] = 1                        # the trash block: finite
        for i in range(b):
            for j, block in enumerate(tables[i]):
                if block != TRASH_BLOCK and j < window_blocks:
                    out[LAYER, block] = rows[i, j * BS:(j + 1) * BS]
        return out

    nan = 0 if pool == "int8" else np.nan        # int8: NaN in the scales
    whole, views = {}, {}
    for name, (rows, scales, _) in stored.items():
        p = pooled(rows, nan)
        whole[name] = jnp.asarray(p.reshape(LAYERS, nb, BS, nkv * d))
        views[name] = jnp.asarray(np.moveaxis(p[LAYER], 2, 0))
        if scales is not None:
            s = pooled(scales, np.nan)
            whole[name + "s"] = jnp.asarray(s)
            views[name + "s"] = jnp.asarray(np.moveaxis(s[LAYER], 2, 0))
    # An idle slot attends the trash block's first rows (ones, scale one).
    seqs = [stored[name][2] for name in ("k", "v")]
    for values in seqs:
        for i, p in enumerate(pos):
            if p is None:
                values[i, :q_len] = 1.0
    q = jnp.asarray(rng.standard_normal((b, q_len, nq, d)), _dtype(pool))
    return {"q": q, "tables": jnp.asarray(tables), "pos": jnp.asarray(first),
            "whole": whole, "views": views, "k_seq": seqs[0],
            "v_seq": seqs[1]}


def _pool_args(case, rep):
    """(k, v, keyword arguments) of an op in one representation: the
    whole token-major pool at ``layer=i``, or a layer's head-major views
    (a tp hook's shard)."""
    arrays = case["whole" if rep == "whole-pool" else "views"]
    kw = {"k_scale": arrays.get("ks"), "v_scale": arrays.get("vs")}
    if rep == "whole-pool":
        kw["layer"] = jnp.int32(LAYER)
    return arrays["k"], arrays["v"], kw


def _check(got, want, q, pool):
    assert got.shape == want.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=TOL[pool], rtol=TOL[pool])


# Skewed lengths at full occupancy: a slot on its first position, one on
# the window's last column, two inside; and an idle slot.
DECODE_POS = [0, MB * BS - 1, 17, 40, None]


@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("rep", ["whole-pool", "head-major-views"])
@pytest.mark.parametrize("op", ["paged_decode", "ragged_verify",
                                "paged_chunk"])
def test_paged_op_matches_the_float64_reference(op, rep, heads, pool):
    if op == "paged_decode":
        case = paged_case(HEADS[heads], pool, DECODE_POS, 1)
        k, v, kw = _pool_args(case, rep)
        got = A.paged_decode(case["q"][:, 0], k, v, case["tables"],
                             case["pos"], **kw)[:, None]
        limit = np.asarray(case["pos"])[:, None]
    elif op == "ragged_verify":
        g = 5                                    # γ + 1 queries a slot
        pos = [0, MB * BS - g, 17, 40, None]
        case = paged_case(HEADS[heads], pool, pos, g)
        k, v, kw = _pool_args(case, rep)
        got = A.ragged_verify(case["q"], k, v, case["tables"], case["pos"],
                              **kw)
        limit = np.asarray(case["pos"])[:, None] + np.arange(g)[None]
    else:
        # One sequence's suffix chunk: 16 queries from position 21, over
        # a window of 3 of the table's 4 blocks (the fourth is NaN).
        s_c, start, window = 16, 21, 3 * BS
        case = paged_case(HEADS[heads], pool, [start], s_c,
                          window_blocks=window // BS)
        k, v, kw = _pool_args(case, rep)
        q_pos = start + np.arange(s_c)[None]
        got = A.paged_chunk(case["q"], k, v, case["tables"][0],
                            jnp.asarray(q_pos), window, **kw)
        limit = q_pos
    want = reference(case["q"], case["k_seq"], case["v_seq"], limit)
    _check(got, want, case["q"], pool)


@pytest.mark.parametrize("pool", POOLS)
@pytest.mark.parametrize("rep", ["whole-pool", "head-major-views"])
def test_a_window_short_of_the_table_reads_no_block_past_it(rep, pool):
    """The windowed tick's contract: tables cut to a rung that covers
    every live position.  Blocks past the window (NaN here, K and V) are
    not gathered, and the answer is the full table's."""
    pos = [0, 2 * BS - 1, 17, 9, None]
    case = paged_case(HEADS["4to1-d64"], pool, pos, 1, window_blocks=2)
    k, v, kw = _pool_args(case, rep)
    got = A.paged_decode(case["q"][:, 0], k, v, case["tables"][:, :2],
                         case["pos"], **kw)[:, None]
    want = reference(case["q"], case["k_seq"], case["v_seq"],
                     np.asarray(case["pos"])[:, None])
    _check(got, want, case["q"], pool)


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
def test_streamed_form_matches_the_float64_reference(pool):
    """The tick an engine that opted into kernels runs over MHA rows of
    whole lane-widths (``decode_form``: ``streamed``; the kernel of
    ops/rows_attention.py, interpreted here)."""
    heads = HEADS["1to1-d64"]
    case = paged_case(heads, pool, DECODE_POS, 1)
    k, v, kw = _pool_args(case, "whole-pool")
    assert A.decode_form("pallas", heads[0], heads[2], MB, BS,
                         k.shape[-1], k.dtype) == "streamed"
    got = A.paged_decode(case["q"][:, 0], k, v, case["tables"], case["pos"],
                         impl="pallas", layer=kw["layer"])[:, None]
    want = reference(case["q"], case["k_seq"], case["v_seq"],
                     np.asarray(case["pos"])[:, None])
    _check(got, want, case["q"], pool)


@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
@pytest.mark.parametrize("op", ["paged_decode", "ragged_verify"])
def test_a_hook_is_handed_the_layers_head_major_views(op, pool):
    """``paged_kv._hooked``: a tp hook's ``(q, kp, vp, tables, pos, ks,
    vs)`` contract over ``[Nkv, NB, bs(, D)]`` views of the traced layer,
    and the op over those views agrees with the op over the whole pool."""
    q_len = 1 if op == "paged_decode" else 3
    pos = [3, 40, 17, 30, None]
    case = paged_case(HEADS["4to1-d64"], pool, pos, q_len)
    whole, views = case["whole"], case["views"]
    pools = tuple(whole[key] for key in ("k", "v", "ks", "vs")
                  if key in whole)
    q = case["q"][:, 0] if op == "paged_decode" else case["q"]
    seen = {}

    def hook(q_, kp, vp, tables, pos_, ks, vs):
        seen.update(k=kp, v=vp, ks=ks, vs=vs)
        return getattr(A, op)(q_, kp, vp, tables, pos_, k_scale=ks,
                              v_scale=vs)

    got = paged_kv._hooked(hook, q, pools, jnp.int32(LAYER), case["tables"],
                           case["pos"])
    for key in ("k", "v", "ks", "vs"):
        if key in views:
            np.testing.assert_array_equal(
                np.asarray(seen[key].astype(jnp.float32)),
                np.asarray(views[key].astype(jnp.float32)))
        else:
            assert seen[key] is None
    k, v, kw = _pool_args(case, "whole-pool")
    over_whole = getattr(A, op)(q, k, v, case["tables"], case["pos"], **kw)
    tol = TOL["float32" if pool == "int8" else pool]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(over_whole, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("cache", POOLS)
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("op", ["decode", "chunk"])
def test_contiguous_op_matches_the_float64_reference(op, heads, cache):
    """``decode`` and ``chunk`` over the sequential engine's contiguous
    cache ``[B, S, Nkv, D]`` (int8 with scales ``[B, S, Nkv]``)."""
    nq, nkv, d = HEADS[heads]
    rng = np.random.default_rng(1)
    b, s = 3, 48
    if op == "decode":
        q_pos = np.array([[0], [s - 1], [17]])
    else:
        q_pos = np.array([[5], [20], [s - 8]]) + np.arange(8)[None]
    stored = {}
    for name, poison in (("k", np.nan), ("v", V_POISON)):
        rows, scales, values = _stored(
            rng.standard_normal((b, s, nkv, d)), cache)
        rows = np.array(rows)
        for i in range(b):
            beyond = q_pos[i, -1] + 1
            values[i, beyond:] = np.nan
            if scales is None:
                rows[i, beyond:] = poison
            else:
                rows[i, beyond:], scales[i, beyond:] = 127, poison
        stored[name] = (jnp.asarray(rows), scales, values)
    q = jnp.asarray(rng.standard_normal((b, q_pos.shape[1], nq, d)),
                    _dtype(cache))
    (k, ks, k_seq), (v, vs, v_seq) = stored["k"], stored["v"]
    kw = {} if ks is None else {"k_scale": jnp.asarray(ks),
                                "v_scale": jnp.asarray(vs)}
    if op == "decode":
        got = A.decode(q[:, 0], k, v, jnp.asarray(q_pos[:, 0]), **kw)[:, None]
    else:
        got = A.chunk(q, k, v, jnp.asarray(q_pos), **kw)
    _check(got, reference(q, k_seq, v_seq, q_pos), q, cache)
