"""``transformer.project_qkv``: the dense family's ONE spelling of a
layer's q, k and v projections (ISSUE 48).

On the CPU the helper's pin is the identity: q, k, v equal the three
lines every dense body used to spell out, bit for bit, for plain and
int8 weights, MHA and GQA, a chunk's rows and a tick's.  What the pin
does to the chip's compiled programs is tests/test_tpu_compile.py's
(``test_dense_programs_read_wq_and_wk_where_they_rest``); here the six
dense bodies are held to CALLING it: each one's jaxpr names the pin once
in its layer body, so a hand-written seventh copy of the three lines
cannot slip past the compile test's three programs.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS
from distributed_llm_tpu.engine import paged_kv
from distributed_llm_tpu.models import transformer
from distributed_llm_tpu.ops import quant

HIDDEN = 128
HEADS = {"mha-32x32x64": (32, 32, 64), "gqa-32x8x64": (32, 8, 64),
         "gqa-32x2x128": (32, 2, 128)}
ROWS = {"chunk": (1, 256, HIDDEN), "tick": (8, HIDDEN)}


def _case(heads, rows, weights):
    nq, nkv, d = HEADS[heads]
    cfg = dataclasses.replace(MODEL_PRESETS["nano_test"], hidden_size=HIDDEN,
                              num_heads=nq, num_kv_heads=nkv, attn_head_dim=d)
    assert cfg.head_dim == d
    keys = jax.random.split(jax.random.PRNGKey(nq * nkv + d), 4)
    lp = {name: (0.05 * jax.random.normal(key, (HIDDEN, n * d), jnp.float32)
                 ).astype(jnp.bfloat16)
          for name, key, n in zip(("wq", "wk", "wv"), keys, (nq, nkv, nkv))}
    if weights == "int8":
        lp = {name: quant.quantize_tensor(w) for name, w in lp.items()}
    h_in = jax.random.normal(keys[3], ROWS[rows], jnp.float32
                             ).astype(jnp.bfloat16)
    return cfg, lp, h_in


@pytest.mark.parametrize("weights", ["bf16", "int8"])
@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("heads", list(HEADS))
def test_project_qkv_is_the_three_lines_bit_for_bit(heads, rows, weights):
    cfg, lp, h_in = _case(heads, rows, weights)
    nq, nkv, d = HEADS[heads]
    lead = h_in.shape[:-1]
    got = jax.jit(lambda lp, h: transformer.project_qkv(cfg, lp, h))(lp, h_in)
    want = jax.jit(lambda lp, h: tuple(
        quant.matmul(h, lp[name]).reshape(*lead, n, d)
        for name, n in (("wq", nq), ("wk", nkv), ("wv", nkv))))(lp, h_in)
    for g, w, n in zip(got, want, (nq, nkv, nkv)):
        assert g.shape == (*lead, n, d) and g.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # The pin stands between the products and the head split: q and k
    # cross it as the [..., N * D] rows the products write.
    jaxpr = jax.make_jaxpr(
        lambda lp, h: transformer.project_qkv(cfg, lp, h))(lp, h_in)
    (pin,) = [e for e in jaxpr.eqns
              if e.primitive.name == "optimization_barrier"]
    assert [v.aval.shape for v in pin.outvars] == [
        (*lead, nq * d), (*lead, nkv * d)]


def _dense_bodies():
    """The six dense bodies on nano_test, each a function of nothing."""
    cfg = MODEL_PRESETS["nano_test"]
    params = transformer.init_params(cfg, seed=3)
    tokens = jnp.asarray([[5 + i for i in range(16)]], jnp.int32)
    positions = jnp.arange(16)[None]
    kv = transformer.init_kv_cache(cfg, 1, 64)
    pcfg = paged_kv.PagedConfig(block_size=16, max_slots=2, max_seq_len=64)
    pool = paged_kv.init_pool(cfg, pcfg)
    tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    return {
        "transformer.prefill": lambda: transformer.prefill(
            cfg, params, tokens, positions),
        "transformer.decode_step": lambda: transformer.decode_step(
            cfg, params, jnp.asarray([7]), jnp.asarray([3]), kv),
        "transformer.chunk_prefill": lambda: transformer.chunk_prefill(
            cfg, params, tokens, jnp.asarray([0]), jnp.asarray([16]), kv,
            window=32),
        "paged_kv.chunk_prefill_paged": lambda: paged_kv.chunk_prefill_paged(
            cfg, params, tokens, jnp.asarray([0]), jnp.asarray([16]), pool,
            tables[0], 32),
        "paged_kv.verify_step_paged": lambda: paged_kv.verify_step_paged(
            cfg, params, jnp.asarray([[7, 8, 9], [4, 5, 6]]),
            jnp.asarray([18, 0]), pool, tables),
        "paged_kv.decode_step_paged": lambda: paged_kv.decode_step_paged(
            cfg, params, jnp.asarray([7, 9]), jnp.asarray([18, 0]), pool,
            tables[:, :2]),
    }


DENSE_BODIES = ["transformer.prefill", "transformer.decode_step",
                "transformer.chunk_prefill", "paged_kv.chunk_prefill_paged",
                "paged_kv.verify_step_paged", "paged_kv.decode_step_paged"]


def _count(jaxpr, primitive):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, primitive)
    return n


@pytest.mark.parametrize("body", DENSE_BODIES)
def test_every_dense_body_projects_through_the_helper(body):
    """One pin a layer body (the layers are ONE scanned body, so one in
    the whole program), and no product against ``wq`` outside it: the
    body's three lines are the helper's."""
    bodies = _dense_bodies()
    assert list(bodies) == DENSE_BODIES
    jaxpr = jax.make_jaxpr(bodies[body])().jaxpr
    assert _count(jaxpr, "optimization_barrier") == 1
    module, name = body.split(".")
    source = {"transformer": transformer, "paged_kv": paged_kv}[module]
    text = inspect.getsource(getattr(source, name))
    assert text.count("project_qkv(cfg, lp, h_in)") == 1
    assert 'lp["wq"]' not in text and 'lp["wk"]' not in text
