"""Checkpoint/resume tests: trainer round-trip, cross-mesh restore, and
the train → serve weight handoff."""

import jax

import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
from distributed_llm_tpu.engine.manager import EngineManager
from distributed_llm_tpu.parallel.mesh import training_mesh
from distributed_llm_tpu.training import TrainConfig, Trainer, batches
from distributed_llm_tpu.utils import checkpoint as ckpt

CFG = MODEL_PRESETS["nano_test"]


def _trainer(devices, seed=0, seq_len=32, batch_size=4):
    mesh = training_mesh(devices, num_kv_heads=CFG.num_kv_heads,
                         seq_len=seq_len)
    return Trainer(CFG, TrainConfig(batch_size=batch_size, seq_len=seq_len,
                                    warmup_steps=2, seed=seed), mesh)


def _leaves_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    return all(np.allclose(np.asarray(x, np.float32),
                           np.asarray(y, np.float32)) for x, y in zip(fa, fb))


def test_trainer_save_load_roundtrip(tmp_path):
    devs = jax.devices()[:4]
    t1 = _trainer(devs, seed=1)
    tokens, mask = next(batches(4, 32, seed=0))
    for _ in range(2):
        t1.train_step(tokens, mask)
    path = t1.save(str(tmp_path / "ckpt"))

    t2 = _trainer(devs, seed=99)             # different init
    assert not _leaves_equal(t1.params, t2.params)
    t2.load(path)
    assert t2.step_count == 2
    assert _leaves_equal(t1.params, t2.params)
    assert _leaves_equal(t1.opt_state, t2.opt_state)

    # Resumed trainer keeps training identically to the original.
    m1 = t1.train_step(tokens, mask)
    m2 = t2.train_step(tokens, mask)
    assert m1["loss"] == pytest.approx(m2["loss"], rel=1e-5)


def test_cross_mesh_restore(tmp_path):
    t_big = _trainer(jax.devices()[:8], seed=3)
    path = t_big.save(str(tmp_path / "ckpt"))
    t_small = _trainer(jax.devices()[:2], seed=4)
    t_small.load(path)                       # reshards at restore time
    assert _leaves_equal(t_big.params, t_small.params)
    tokens, mask = next(batches(4, 32, seed=1))
    assert np.isfinite(t_small.train_step(tokens, mask)["loss"])


def test_train_then_serve_from_checkpoint(tmp_path):
    t = _trainer(jax.devices()[:2], seed=5)
    tokens, mask = next(batches(4, 32, seed=2))
    t.train_step(tokens, mask)
    path = t.save(str(tmp_path / "weights"))

    tier = TierConfig(name="nano", model_preset="nano_test",
                      max_new_tokens=6, prefill_buckets=(16, 32),
                      checkpoint_path=path)
    mgr = EngineManager(tier, warmup_on_start=False)
    engine = mgr.engine()
    assert _leaves_equal(engine.params, t.params)
    r = engine.generate("user: hello", max_new_tokens=4)
    assert r.gen_tokens >= 0 and isinstance(r.text, str)
    mgr.stop_server()


def test_abstract_params_matches_real_init():
    sd = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    abstract = ckpt.abstract_params(CFG, sd)
    real = jax.jit(lambda: __import__(
        "distributed_llm_tpu.models.transformer",
        fromlist=["transformer"]).init_params(CFG, seed=0))()
    ab_leaves = jax.tree.leaves(abstract)
    re_leaves = jax.tree.leaves(real)
    assert [(a.shape, a.dtype) for a in ab_leaves] == \
        [(r.shape, r.dtype) for r in re_leaves]
    assert all(a.sharding == sd for a in ab_leaves)


def test_versioned_saves_keep_latest_and_prune(tmp_path):
    import os
    t = _trainer(jax.devices()[:2], seed=6)
    tokens, mask = next(batches(4, 32, seed=3))
    root = str(tmp_path / "ckpt")
    for _ in range(3):
        t.train_step(tokens, mask)
        t.save(root)
    versions = sorted(d for d in os.listdir(root) if d.startswith("v"))
    assert versions == ["v2", "v3"]          # max_to_keep=2, oldest pruned
    assert os.path.islink(os.path.join(root, "latest"))
    assert os.path.realpath(os.path.join(root, "latest")).endswith("v3")

    t2 = _trainer(jax.devices()[:2], seed=7)
    t2.load(root)
    assert t2.step_count == 3


def test_save_replaces_stale_same_step_version(tmp_path):
    """A rolled-back/abandoned run can leave a v<step> directory that a
    retry reaches again at the same global step; the save force-
    overwrites the stale version.  But when v<step> IS the live
    published 'latest' (save_every divided max_steps, so the loop save
    and the final save coincide), re-saving is a NO-OP — an in-place
    rewrite of the live artifact would break the kill-at-any-instant
    invariant for identical state."""
    import os
    t = _trainer(jax.devices()[:1], seed=11)
    tokens, mask = next(batches(4, 32, seed=4))
    root = str(tmp_path / "ckpt")
    t.train_step(tokens, mask)
    t.save(root)                                    # publishes v1
    # Stale same-step dir from an abandoned run, NOT the published one.
    t.train_step(tokens, mask)
    stale = os.path.join(root, "v2")
    os.makedirs(os.path.join(stale, "state"))
    with open(os.path.join(stale, "state", "junk"), "w") as f:
        f.write("stale")
    t.save(root)                                    # replaces v2
    assert os.path.realpath(os.path.join(root, "latest")).endswith("v2")
    assert not os.path.exists(os.path.join(root, "v2", "state", "junk"))
    t2 = _trainer(jax.devices()[:1], seed=12)
    t2.load(root)
    assert t2.step_count == 2

    # Same-step REPUBLISH of the live artifact: untouched, still loads.
    before = os.stat(os.path.join(root, "v2", "state")).st_mtime_ns
    t.save(root)
    assert os.stat(os.path.join(root, "v2", "state")).st_mtime_ns == before
    t3 = _trainer(jax.devices()[:1], seed=13)
    t3.load(root)
    assert t3.step_count == 2


def test_peek_vocab_size_reads_metadata_only():
    """A stale-vocab guard before serving or resuming a checkpoint
    depends on this returning the real embed row count (the orbax
    metadata pytree lives under item_metadata.tree)."""
    from distributed_llm_tpu.config import MODEL_PRESETS, default_checkpoint
    from distributed_llm_tpu.utils.checkpoint import peek_vocab_size
    ckpt = default_checkpoint("nano_test")
    if ckpt is None:
        import pytest
        pytest.skip("checkpoints/nano_test not published")
    assert peek_vocab_size(ckpt) == MODEL_PRESETS["nano_test"].vocab_size
    assert peek_vocab_size("checkpoints/definitely_missing") is None
