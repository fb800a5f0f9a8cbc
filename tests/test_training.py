"""Training subsystem tests: sharded train step on a virtual CPU mesh.

The reference has no training (SURVEY.md §2.1 — models live in Ollama); this
validates the new TPU-native capability: FSDP×SP×TP mesh factorization,
sharding placement, loss decrease, and determinism of the data pipeline.
"""

import jax

import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS
from distributed_llm_tpu.parallel.mesh import training_mesh
from distributed_llm_tpu.training import TrainConfig, Trainer, batches, synthetic_text


CFG = MODEL_PRESETS["nano_test"]


def test_training_mesh_uses_all_devices():
    mesh = training_mesh(num_kv_heads=CFG.num_kv_heads, seq_len=64)
    assert mesh.size == len(jax.devices())
    assert set(mesh.axis_names) == {"dp", "sp", "tp"}
    # tp must divide kv heads
    assert CFG.num_kv_heads % mesh.shape["tp"] == 0


def test_data_pipeline_deterministic():
    a = next(batches(4, 32, seed=7))
    b = next(batches(4, 32, seed=7))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    c = next(batches(4, 32, seed=8))
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (4, 32) and a[1].dtype == np.float32


def test_synthetic_text_nonempty():
    rng = np.random.default_rng(0)
    text = synthetic_text(rng)
    assert len(text) > 20 and "." in text


@pytest.fixture(scope="module")
def trainer():
    mesh = training_mesh(num_kv_heads=CFG.num_kv_heads, seq_len=64)
    return Trainer(CFG, TrainConfig(batch_size=8, seq_len=64, warmup_steps=2),
                   mesh)


def test_params_are_sharded_fsdp_tp(trainer):
    mesh = trainer.mesh
    if mesh.shape["dp"] > 1:
        spec = trainer.params["embed"].sharding.spec
        assert spec[0] == "dp"
    if mesh.shape["tp"] > 1:
        spec = trainer.params["layers"]["wq"].sharding.spec
        assert spec[-1] == "tp"


def test_loss_decreases_over_steps(trainer):
    it = batches(8, 64, seed=3)
    losses = []
    for _ in range(15):
        toks, mask = next(it)
        m = trainer.train_step(toks, mask)
        losses.append(m["loss"])
        assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
    assert losses[-1] < losses[0], losses


def test_loss_mask_excludes_padding(trainer):
    # All-pad rows with zero mask must yield a finite loss (denominator guard)
    toks = np.full((8, 64), 256, np.int32)
    mask = np.zeros((8, 64), np.float32)
    m = trainer.train_step(toks, mask)
    assert np.isfinite(m["loss"])


def test_graft_entry_dryrun():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", "__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(len(jax.devices()))


def test_trainer_on_subset_meshes():
    """Docstring contract: any subset of ('dp','sp','tp') axes works."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2])
    for axes in (("dp",), ("tp",)):
        mesh = Mesh(devs.reshape(2), axes)
        tr = Trainer(CFG, TrainConfig(batch_size=4, seq_len=32,
                                      warmup_steps=2), mesh)
        toks, mask = next(batches(4, 32, seed=0))
        m = tr.train_step(toks, mask)
        assert np.isfinite(m["loss"]), (axes, m)


def test_training_mesh_odd_device_counts():
    """All devices used for non-power-of-2 counts (no silent dropping)."""
    mesh6 = training_mesh(jax.devices()[:6], num_kv_heads=2, seq_len=64)
    assert mesh6.size == 6, dict(mesh6.shape)
    mesh5 = training_mesh(jax.devices()[:5], num_kv_heads=2, seq_len=64)
    assert mesh5.size == 5, dict(mesh5.shape)


# -- corpus data pipeline ----------------------------------------------------

def test_pack_documents_dense_with_eos():
    import numpy as np
    from distributed_llm_tpu.engine.tokenizer import ByteTokenizer
    from distributed_llm_tpu.training import pack_documents
    tok = ByteTokenizer()
    rows = pack_documents(["hello world", "second doc"], seq_len=8)
    flat = rows.reshape(-1).tolist()
    assert tok.eos_id in flat                 # documents separated by EOS
    assert rows.dtype == np.int32
    assert (rows != tok.pad_id).all()         # packing leaves no padding
    import pytest
    with pytest.raises(ValueError, match="too small"):
        pack_documents(["x"], seq_len=4096)


def test_corpus_batches_trains_from_files(tmp_path):
    import numpy as np
    from distributed_llm_tpu.training import corpus_batches
    corpus = tmp_path / "corpus.txt"
    docs = "\n\n".join(
        f"document {i}: the mesh routes tokens across links while cores "
        f"multiply matrices and kernels fuse." for i in range(30))
    corpus.write_text(docs)

    it = corpus_batches([str(corpus)], batch_size=2, seq_len=64, seed=0,
                        loop=False)
    batches_list = list(it)
    assert len(batches_list) >= 2
    toks, mask = batches_list[0]
    assert toks.shape == (2, 64) and mask.shape == (2, 64)
    assert mask.all()

    # Deterministic given the seed; reshuffled across epochs.
    again = list(corpus_batches([str(corpus)], batch_size=2, seq_len=64,
                                seed=0, loop=False))
    np.testing.assert_array_equal(batches_list[0][0], again[0][0])

    # And it actually trains.
    import jax
    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.parallel.mesh import training_mesh
    from distributed_llm_tpu.training import TrainConfig, Trainer
    cfg = MODEL_PRESETS["nano_test"]
    mesh = training_mesh(jax.devices()[:2], num_kv_heads=cfg.num_kv_heads,
                         seq_len=64)
    trainer = Trainer(cfg, TrainConfig(batch_size=2, seq_len=64,
                                       warmup_steps=2), mesh)
    it = corpus_batches([str(corpus)], batch_size=2, seq_len=64, seed=1)
    losses = [trainer.train_step(*next(it))["loss"] for _ in range(4)]
    assert losses[-1] < losses[0]
