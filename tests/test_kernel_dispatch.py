"""The served tick's attention: one rule (``ops.attention.decode_form``,
static shapes and the engine's ``attention_impl``) between its two forms,
and both forms against ``decode_attention``.
"""

import pytest

from distributed_llm_tpu.ops import attention as A


@pytest.fixture(autouse=True)
def no_override(monkeypatch):
    monkeypatch.delenv("DLLM_ATTENTION", raising=False)


def test_auto_stays_xla():
    # 'auto' (sharded/portable engines) never takes a kernel, whatever
    # the shapes would serve — a pallas_call has no GSPMD rule.
    import jax.numpy as jnp
    assert A.resolve_impl("auto") == "xla"
    assert A.decode_form("auto", 32, 64, 4, 64, 2048,
                         jnp.bfloat16) == "merged"


# -- the form follows the representation (ISSUE 30) ---------------------------

# bfloat16: what chip_smoke.KERNEL_TOL holds the Pallas kernels to against
# their XLA references (2e-2: the two round at different points).
_FORM_TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _token_major_case(n_q, n_kv, d, dtype, int8, *, batch=4, bs=16, mb=8,
                      layers=3, seed=0):
    """A WHOLE token-major pool ``[L, NB, bs, Nkv * D]`` (int8 with its
    scales ``[L, NB, bs, Nkv]``) and a batch whose slot 0 is idle (``pos``
    0, a table of trash blocks), slot 1 attends the last column of the
    window, the others somewhere inside."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_tpu.engine.paged_kv import TRASH_BLOCK
    from distributed_llm_tpu.ops.quant import quantize_kv_rows
    nb = batch * mb + 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (batch, n_q, d), jnp.float32).astype(dtype)
    pools = [jax.random.normal(k, (layers, nb, bs, n_kv, d), jnp.float32)
             for k in keys[1:]]
    scales = (None, None)
    if int8:
        (k, ks), (v, vs) = (quantize_kv_rows(p) for p in pools)
        pools, scales = [k, v], (ks, vs)
    else:
        pools = [p.astype(dtype) for p in pools]
    pools = [p.reshape(layers, nb, bs, n_kv * d) for p in pools]
    tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(batch, mb)
    tables = tables.at[0].set(TRASH_BLOCK)
    pos = jnp.array([0, mb * bs - 1, 17, 40][:batch], jnp.int32)
    return q, pools, scales, tables, pos


# What the served tick's two forms are asked for: ``merged`` the XLA path
# every engine off the chip or on a mesh takes (impl 'xla'); ``streamed``
# an engine that opted into kernels: the kernel of ops/rows_attention.py,
# interpreted here.
_SERVED_FORMS = {"merged": "xla", "streamed": "pallas"}


@pytest.mark.parametrize("form", list(_SERVED_FORMS))
@pytest.mark.parametrize("pool", ["model-dtype-pool", "int8-pool"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_q,n_kv,d", [(32, 32, 64), (32, 8, 64),
                                        (32, 8, 128)],
                         ids=["mha-d64", "gqa-d64", "gqa-d128"])
def test_served_forms_agree_with_decode_attention(n_q, n_kv, d, dtype,
                                                  pool, form):
    """The served tick's attention (``layer=i``: the whole token-major
    pool) in both its forms — the XLA gather attended merged, and the
    block table walked by the kernel with nothing gathered (ISSUE 45) —
    against ``decode_attention`` over the SAME rows with the head axis
    split off, the reference the Pallas kernels are held to as well: MHA
    and GQA at head 64, GQA at head 128, an int8 pool with its scales
    (GQA and int8 the kernel does not serve: the rule sends them to the
    XLA form whatever the engine opted into), an idle slot over the trash
    block, ``pos`` at 0 and at the window's last position."""
    import jax.numpy as jnp
    import numpy as np
    impl = _SERVED_FORMS[form]
    int8 = pool == "int8-pool"
    q, (kp, vp), (ks, vs), tables, pos = _token_major_case(
        n_q, n_kv, d, jnp.dtype(dtype), int8)
    assert A.decode_form(
        impl, n_q, d, tables.shape[1], kp.shape[2], kp.shape[3], kp.dtype
    ) == (form if n_kv == n_q and not int8 else "merged")
    layer = jnp.int32(1)
    got = A.paged_decode(q, kp, vp, tables, pos, impl=impl, k_scale=ks,
                         v_scale=vs, layer=layer)
    k_seq, v_seq = A._gather_pool_seq(q, kp, vp, tables, ks, vs, layer)
    assert k_seq.shape == (*tables.shape[:1], tables.shape[1] * kp.shape[2],
                           n_kv, d)
    want = A.decode_attention(q, k_seq, v_seq, pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = _FORM_TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # The idle slot attends one trash row: its value, whatever the rest.
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32),
        np.asarray(jnp.repeat(v_seq[0, 0], n_q // n_kv, axis=0), np.float32),
        atol=tol, rtol=tol)


def test_streamed_form_is_chosen_from_static_shapes(monkeypatch):
    """``decode_form``: the kernel takes the tick where the engine opted
    into kernels, every query head has a K/V head of its own and the rows
    fill whole lanes; ``DLLM_ATTENTION`` switches every kernel off or
    forces the opt-in, and nothing else does: two outcomes."""
    import jax.numpy as jnp
    bf16 = jnp.bfloat16
    args = (32, 64, 4, 64)
    assert A.decode_form("pallas", *args, 2048, bf16) == "streamed"
    assert A.decode_form("auto", *args, 2048, bf16) == "merged"
    assert A.decode_form("pallas", *args, 2048, jnp.int8) == "merged"
    # Query heads that share a K/V head (GQA 32/8: rows of 512 columns).
    assert A.decode_form("pallas", *args, 512, bf16) == "merged"
    # A row off the lanes (a tiny preset's), a block off the sublanes.
    assert A.decode_form("pallas", 3, 32, 4, 64, 96, bf16) == "merged"
    assert A.decode_form("pallas", 32, 64, 4, 8, 2048, bf16) == "merged"
    monkeypatch.setenv("DLLM_ATTENTION", "xla")
    assert A.decode_form("pallas", *args, 2048, bf16) == "merged"
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    assert A.decode_form("auto", *args, 2048, bf16) == "streamed"


