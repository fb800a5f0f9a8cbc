"""Mesh carving, TP sharding, collectives, ring attention — on the 8-device
virtual CPU mesh (no TPU required; SURVEY.md §4 implication)."""

from functools import partial

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_llm_tpu.config import (MODEL_PRESETS, ClusterConfig,
                                        TierConfig, tiny_cluster)
from distributed_llm_tpu.engine.inference import InferenceEngine
from distributed_llm_tpu.models import transformer
from distributed_llm_tpu.ops.attention import causal_attention
from distributed_llm_tpu.parallel.collectives import (
    allgather_health, psum_scalar, summarize_perf_window)
from distributed_llm_tpu.parallel.mesh import carve_tier_meshes, tp_mesh
from distributed_llm_tpu.parallel.ring_attention import ring_attention
from distributed_llm_tpu.parallel.sharding import (
    param_shardings, param_specs)


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8
    assert jax.default_backend() == "cpu"


# -- mesh carving -----------------------------------------------------------

def test_carve_disjoint_submeshes():
    meshes = carve_tier_meshes(tiny_cluster())
    nano_ids = {d.id for d in meshes["nano"].devices.flat}
    orin_ids = {d.id for d in meshes["orin"].devices.flat}
    assert len(nano_ids) == 1 and len(orin_ids) == 4
    assert nano_ids.isdisjoint(orin_ids)


def test_carve_single_device_shares():
    meshes = carve_tier_meshes(tiny_cluster(), devices=jax.devices()[:1])
    assert len(list(meshes["nano"].devices.flat)) == 1
    assert len(list(meshes["orin"].devices.flat)) == 1


def test_carve_shrinks_to_divisor_of_heads():
    # orin_test has 4 kv heads; with 3 devices left, tp shrinks to 2
    cluster = ClusterConfig(
        nano=TierConfig(name="nano", model_preset="nano_test", tp=1),
        orin=TierConfig(name="orin", model_preset="orin_test", tp=4))
    meshes = carve_tier_meshes(cluster, devices=jax.devices()[:4])
    assert len(list(meshes["orin"].devices.flat)) == 2


# -- TP sharding ------------------------------------------------------------

def test_param_specs_match_param_tree():
    cfg = MODEL_PRESETS["orin_test"]
    params = transformer.init_params(cfg, seed=0)
    specs = param_specs(cfg)
    jax.tree.map(lambda p, s: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_tp_sharded_prefill_matches_single_device():
    cfg = MODEL_PRESETS["orin_test"]
    tokens = jnp.array([[257, 72, 101, 108, 108, 111, 33, 10]])
    pos = jnp.arange(tokens.shape[1])[None]

    params = transformer.init_params(cfg, seed=5)
    h_ref, _ = transformer.prefill(cfg, params, tokens, pos)

    mesh = tp_mesh(jax.devices(), 4)
    sharded = jax.device_put(params, param_shardings(cfg, mesh))
    h_tp, (k_tp, _) = jax.jit(partial(transformer.prefill, cfg))(
        sharded, tokens, pos)

    np.testing.assert_allclose(np.asarray(h_tp, np.float32),
                               np.asarray(h_ref, np.float32),
                               rtol=5e-2, atol=5e-2)
    # K cache heads actually sharded over tp
    assert not k_tp.sharding.is_fully_replicated


def test_tp_rejects_indivisible_heads():
    cfg = MODEL_PRESETS["nano_test"]   # 2 kv heads
    mesh = tp_mesh(jax.devices(), 4)
    with pytest.raises(ValueError):
        param_shardings(cfg, mesh)


def test_engine_on_tp_mesh_generates():
    tier = tiny_cluster().orin
    mesh = tp_mesh(jax.devices(), 4)
    eng = InferenceEngine(tier, seed=0, mesh=mesh)
    r = eng.generate("user: hello from the mesh")
    assert r.gen_tokens >= 0 and r.total_ms > 0
    # params are actually distributed
    wq = eng.params["layers"]["wq"]
    assert len(wq.sharding.device_set) == 4


def test_tp_engine_matches_single_device_tokens():
    tier = tiny_cluster().orin
    single = InferenceEngine(tier, seed=3)
    tp = InferenceEngine(tier, seed=3, mesh=tp_mesh(jax.devices(), 4))
    a = single.generate("user: compare me")
    b = tp.generate("user: compare me")
    assert a.token_ids == b.token_ids


# -- collectives ------------------------------------------------------------

def test_allgather_health_roundtrip():
    mesh = tp_mesh(jax.devices(), 8, axis_name="ici")
    rows = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
    out = allgather_health(mesh, rows)
    np.testing.assert_allclose(out, rows)


def test_allgather_health_row_mismatch():
    mesh = tp_mesh(jax.devices(), 4, axis_name="ici")
    with pytest.raises(ValueError):
        allgather_health(mesh, np.zeros((3, 4), np.float32))


def test_psum_scalar_counts_quorum():
    mesh = tp_mesh(jax.devices(), 8, axis_name="ici")
    alive = np.ones(8, np.float32)
    assert psum_scalar(mesh, alive) == 8.0


def test_summarize_perf_window():
    samples = [(100.0, 10, True), (200.0, 0, False)]
    row = summarize_perf_window(samples)
    np.testing.assert_allclose(row, [300.0, 10.0, 1.0, 2.0])


# -- ring attention ---------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("groups", [1, 2])
def test_ring_attention_matches_reference(causal, groups):
    mesh = tp_mesh(jax.devices(), 4, axis_name="sp")
    b, s, n_q, d = 2, 32, 4, 16
    n_kv = n_q // groups
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n_q, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, n_kv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, s, n_kv, d), jnp.float32)

    out_ring = ring_attention(q, k, v, mesh, axis_name="sp", causal=causal)

    if causal:
        out_ref = causal_attention(q, k, v)
    else:
        groups_e = n_q // n_kv
        from distributed_llm_tpu.ops.attention import _expand_kv
        ke, ve = _expand_kv(k, groups_e), _expand_kv(v, groups_e)
        logits = jnp.einsum("bqnd,bknd->bnqk", q, ke) * d ** -0.5
        out_ref = jnp.einsum("bnqk,bknd->bqnd",
                             jax.nn.softmax(logits, -1), ve)

    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_sequence_stays_sharded():
    mesh = tp_mesh(jax.devices(), 4, axis_name="sp")
    b, s, n, d = 1, 16, 2, 8
    x = jnp.ones((b, s, n, d), jnp.float32)
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    q = jax.device_put(x, spec)
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(q, q, q)
    assert not out.sharding.is_fully_replicated


def test_sp_prefill_engine_matches_single_device_tokens(monkeypatch):
    """Sequence-parallel ring prefill (sp=4 tier mesh) must generate the
    same greedy tokens as the unsharded engine — ring attention changes
    where the O(S²) work runs, not its result.  Asserts the ring op
    actually ran (a prompt that misses the bucketed path would compare
    chunked-vs-chunked and pass vacuously)."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.parallel import ring_attention as ra
    from distributed_llm_tpu.parallel.mesh import sp_tp_mesh

    calls = []
    real = ra.ring_attention
    monkeypatch.setattr(ra, "ring_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))

    tier = TierConfig(name="nano", model_preset="nano_test",
                      max_new_tokens=6, prefill_buckets=(16, 32, 64))
    single = InferenceEngine(tier, seed=13)
    sp = InferenceEngine(tier, seed=13,
                         mesh=sp_tp_mesh(jax.devices(), sp=4, tp=1))
    prompt = "user: short enough to fit one bucket"   # 41 ids -> bucket 64
    a = single.generate(prompt)
    assert not calls                                  # unsharded: no ring
    b = sp.generate(prompt)
    assert calls, "sp engine never invoked ring attention"
    assert a.token_ids == b.token_ids


def test_sp_engine_serves_long_prompt_via_ring_not_chunks(monkeypatch):
    """Prompts beyond the tier's largest configured bucket — THE case sp
    exists for — must take the extended-ladder ring prefill on an sp tier,
    and still match the unsharded engine's chunk-stride output."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.parallel import ring_attention as ra
    from distributed_llm_tpu.parallel.mesh import sp_tp_mesh

    calls = []
    real = ra.ring_attention
    monkeypatch.setattr(ra, "ring_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))

    tier = TierConfig(name="nano", model_preset="nano_test",
                      max_new_tokens=6, prefill_buckets=(16, 32, 64))
    single = InferenceEngine(tier, seed=19)
    sp = InferenceEngine(tier, seed=19,
                         mesh=sp_tp_mesh(jax.devices(), sp=4, tp=1))
    # 120 ids: past bucket 64, within max_seq 256 — sp ladder covers it.
    prompt = "user: " + "tell me about sequence parallel rings " * 3
    assert sp._buckets[-1] == 256                     # ladder reaches max_seq
    a = single.generate(prompt)                       # chunk-stride path
    b = sp.generate(prompt)                           # one ring prefill
    assert calls, "long prompt did not use ring attention on the sp tier"
    assert a.token_ids == b.token_ids


def test_sp_tp_2d_mesh_prefill_matches_single_device_tokens():
    """2-D sp×tp tier mesh: ring attention over 'sp' with heads sharded
    over 'tp' (orin_test has 4 kv heads)."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.parallel.mesh import sp_tp_mesh

    tier = TierConfig(name="orin", model_preset="orin_test",
                      max_new_tokens=6, prefill_buckets=(16, 32, 64))
    single = InferenceEngine(tier, seed=17)
    both = InferenceEngine(tier, seed=17,
                           mesh=sp_tp_mesh(jax.devices(), sp=2, tp=2))
    prompt = "user: compare the two dimensional mesh against one chip"
    a = single.generate(prompt)
    b = both.generate(prompt)
    assert a.token_ids == b.token_ids


def test_carve_assigns_2d_mesh_for_sp_tier():
    from distributed_llm_tpu.config import ClusterConfig, TierConfig
    from distributed_llm_tpu.parallel.mesh import carve_tier_meshes

    cluster = ClusterConfig(
        nano=TierConfig(name="nano", model_preset="nano_test", tp=1),
        orin=TierConfig(name="orin", model_preset="orin_test", tp=2, sp=2))
    meshes = carve_tier_meshes(cluster)
    assert dict(meshes["orin"].shape) == {"sp": 2, "tp": 2}
    # Chips are disjoint: nano got 1, orin the next 4.
    nano_ids = {d.id for d in meshes["nano"].devices.flat}
    orin_ids = {d.id for d in meshes["orin"].devices.flat}
    assert not nano_ids & orin_ids


# -- sequence-parallel decode (parallel/sp_attention.py) --------------------

def test_sp_decode_matches_unsharded_tokens():
    """The 'sp'-sharded-cache decode (per-shard partials + log-sum-exp
    merge) produces the same greedy tokens as the single-device engine —
    and the engine really holds its cache sequence-sharded, which is the
    capacity point: S/sp cached positions per chip."""
    import dataclasses

    from distributed_llm_tpu.config import tiny_cluster
    from distributed_llm_tpu.engine.inference import InferenceEngine
    from distributed_llm_tpu.parallel.mesh import sp_tp_mesh

    tier = dataclasses.replace(tiny_cluster().orin, tp=1, sp=4,
                               max_new_tokens=8)
    ref = InferenceEngine(tier, seed=7)
    sp = InferenceEngine(tier, seed=7,
                         mesh=sp_tp_mesh(jax.devices(), sp=4, tp=1))
    assert sp._sp_shard and sp.prefix_cache is None
    prompt = ("user: " + "the mesh routes tokens and the compiler fuses "
              "kernels. " * 6).strip()
    assert ref.generate(prompt).token_ids == sp.generate(prompt).token_ids


def test_sp_decode_cache_is_sequence_sharded():
    import dataclasses

    from distributed_llm_tpu.config import tiny_cluster
    from distributed_llm_tpu.engine.inference import InferenceEngine
    from distributed_llm_tpu.parallel.mesh import sp_tp_mesh

    tier = dataclasses.replace(tiny_cluster().orin, tp=1, sp=4,
                               max_new_tokens=4)
    sp = InferenceEngine(tier, seed=3,
                         mesh=sp_tp_mesh(jax.devices(), sp=4, tp=1))
    fn = sp._prefill_fn(32, sp._pick_cache_len(40))
    import numpy as np
    tokens = np.full((1, 32), sp.tokenizer.pad_id, np.int32)
    first, cache = fn(sp.params, jnp.asarray(tokens),
                      jnp.asarray([4], np.int32), jax.random.PRNGKey(0),
                      jnp.float32(0.0))
    # [L, B, S, N_kv, D]: the SEQUENCE axis carries 'sp'.
    assert cache["k"].sharding.spec[2] == "sp", cache["k"].sharding


def test_sp_flash_decode_merge_matches_reference_math():
    """Direct op check: sharded partial+merge == full-cache softmax."""
    from distributed_llm_tpu.ops.attention import decode_attention
    from distributed_llm_tpu.parallel.sp_attention import sp_flash_decode

    devs = jax.devices()[:4]
    mesh = jax.sharding.Mesh(np.asarray(devs).reshape(4), ("sp",))
    b, s, nkv, nq, d = 2, 64, 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, nq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
    pos = jnp.asarray([3, 50], jnp.int32)   # one shard-0-only, one deep
    got = sp_flash_decode(mesh)(q, k, v, pos)
    want = decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_sp_decode_budget_scales_context_capacity():
    """An orin_8b tier at sp=4 holds a quarter of the cache per chip —
    the long-context capacity story (utils/hbm_budget.py)."""
    import dataclasses

    from distributed_llm_tpu.config import flagship_cluster
    from distributed_llm_tpu.utils.hbm_budget import tier_hbm_budget

    # decode_batch=1: sp decode shards the SEQUENTIAL engine's dense
    # cache (parallel/sp_attention.py); the batched paged pool shards
    # its kv-head axis over tp instead (the flagship orin preset is
    # batched these days, so pin the engine the story is about).
    base = dataclasses.replace(flagship_cluster(n_devices=8).orin, tp=1,
                               quantize="none", enable_prefix_cache=False,
                               decode_batch=1)
    b1 = tier_hbm_budget(dataclasses.replace(base, sp=1))
    b4 = tier_hbm_budget(dataclasses.replace(base, sp=4))
    # (reported values round to 3 decimals)
    assert abs(b4["kv_gb_per_chip"] - b1["kv_gb_per_chip"] / 4) < 1e-3


def test_sp_tp_2d_decode_matches_unsharded_tokens():
    """The 2-D tier mesh ('sp','tp'): ring prefill over sp, decode over
    the sequence-sharded cache with head-sharded q/kv over tp — token
    parity with the single-device engine across both axes at once."""
    import dataclasses

    from distributed_llm_tpu.config import tiny_cluster
    from distributed_llm_tpu.engine.inference import InferenceEngine
    from distributed_llm_tpu.parallel.mesh import sp_tp_mesh

    tier = dataclasses.replace(tiny_cluster().orin, tp=2, sp=2,
                               max_new_tokens=8)
    ref = InferenceEngine(dataclasses.replace(tier, tp=1, sp=1), seed=7)
    grid = InferenceEngine(tier, seed=7,
                           mesh=sp_tp_mesh(jax.devices(), sp=2, tp=2))
    assert grid._sp_shard
    prompt = ("user: " + "the mesh routes tokens and the compiler fuses "
              "kernels. " * 6).strip()
    assert ref.generate(prompt).token_ids == grid.generate(prompt).token_ids


def test_sp_decode_composes_with_int8_weights():
    """sp-sharded-cache decode over int8 weights (quantized sharding
    rules on the 2-D ('sp','tp') mesh): token parity with the unsharded
    int8 engine."""
    import dataclasses

    from distributed_llm_tpu.config import tiny_cluster
    from distributed_llm_tpu.engine.inference import InferenceEngine
    from distributed_llm_tpu.parallel.mesh import sp_tp_mesh

    tier = dataclasses.replace(tiny_cluster().orin, tp=1, sp=4,
                               quantize="int8", max_new_tokens=8)
    ref = InferenceEngine(tier, seed=7)
    sp = InferenceEngine(tier, seed=7,
                         mesh=sp_tp_mesh(jax.devices(), sp=4, tp=1))
    prompt = ("user: " + "the mesh routes tokens and the compiler fuses "
              "kernels. " * 6).strip()
    assert ref.generate(prompt).token_ids == sp.generate(prompt).token_ids
