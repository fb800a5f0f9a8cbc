"""Pipeline parallelism: GPipe schedule correctness (forward + gradients)
and the pipeline-parallel transformer trainer."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llm_tpu.config import MODEL_PRESETS
from distributed_llm_tpu.parallel.pipeline import (merge_stages,
                                                   pipeline_apply,
                                                   split_stages)
from distributed_llm_tpu.training import TrainConfig, batches
from distributed_llm_tpu.training.pipeline_trainer import (PipelineTrainer,
                                                           pipeline_lm_loss)
from distributed_llm_tpu.training.trainer import Trainer, lm_loss


def _pp_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("pp",))


def _simple_stage(lp_stack, x, extras):
    # Each "layer" is x -> tanh(x @ w); scan over this stage's layers.
    def layer(x, w):
        return jnp.tanh(x @ w), None
    x, _ = jax.lax.scan(layer, x, lp_stack)
    return x


def test_split_merge_roundtrip():
    layers = {"w": jnp.arange(24.0).reshape(8, 3)}
    staged = split_stages(layers, 4)
    assert staged["w"].shape == (4, 2, 3)
    np.testing.assert_array_equal(merge_stages(staged)["w"], layers["w"])
    with pytest.raises(ValueError, match="divisible"):
        split_stages(layers, 3)


def test_pipeline_forward_matches_sequential():
    l, h = 8, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (l, h, h)) * 0.3
    mbs = jax.random.normal(jax.random.PRNGKey(1), (3, 4, h))  # M=3, mb=4

    # Sequential reference: all layers in order.
    ref = mbs
    for i in range(l):
        ref = jnp.tanh(ref @ ws[i])

    for stages in (2, 4):
        mesh = _pp_mesh(stages)
        got = pipeline_apply(mesh, _simple_stage,
                             split_stages({"": ws}, stages)[""], mbs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_pipeline_gradients_match_sequential():
    l, h = 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(2), (l, h, h)) * 0.3
    mbs = jax.random.normal(jax.random.PRNGKey(3), (2, 4, h))
    mesh = _pp_mesh(4)

    def loss_pipe(ws):
        out = pipeline_apply(mesh, _simple_stage, split_stages({"": ws}, 4)[""],
                             mbs)
        return jnp.sum(out ** 2)

    def loss_seq(ws):
        x = mbs
        for i in range(l):
            x = jnp.tanh(x @ ws[i])
        return jnp.sum(x ** 2)

    g_pipe = jax.grad(loss_pipe)(ws)
    g_seq = jax.grad(loss_seq)(ws)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_lm_loss_matches_dense_loss():
    """Same weights, same batch: the pipelined forward must produce the
    same loss as the plain scanned forward."""
    cfg = MODEL_PRESETS["nano_test"]
    mesh = _pp_mesh(2)
    tokens, mask = next(batches(4, 32, seed=0))
    tokens, mask = jnp.asarray(tokens), jnp.asarray(mask)

    from distributed_llm_tpu.models import transformer
    base = transformer.init_params(cfg, seed=5)
    staged = {**base, "layers": split_stages(base["layers"], 2)}
    pipe = pipeline_lm_loss(cfg, staged, tokens, mask, mesh,
                            num_microbatches=2)
    dense = lm_loss(cfg, base, tokens, mask, remat=False)
    assert float(pipe) == pytest.approx(float(dense), rel=1e-4)


def test_pipeline_trainer_learns_and_shards_stages():
    cfg = MODEL_PRESETS["nano_test"]
    mesh = _pp_mesh(2)
    trainer = PipelineTrainer(cfg, TrainConfig(batch_size=4, seq_len=32,
                                               warmup_steps=2), mesh,
                              num_microbatches=2)
    spec = trainer.params["layers"]["wq"].sharding.spec
    assert spec[0] == "pp"
    tokens, mask = next(batches(4, 32, seed=1))
    losses = [trainer.train_step(tokens, mask)["loss"] for _ in range(3)]
    assert all(np.isfinite(x) for x in losses)
    assert losses[-1] < losses[0]

    exported = trainer.export_params()
    assert exported["layers"]["wq"].shape[0] == cfg.num_layers


def test_pipeline_trainer_validates_config():
    cfg = MODEL_PRESETS["nano_test"]
    with pytest.raises(ValueError, match="'pp' axis"):
        PipelineTrainer(cfg, TrainConfig(batch_size=4, seq_len=32),
                        Mesh(np.array(jax.devices()[:2]), ("dp",)))
    with pytest.raises(ValueError, match="not divisible"):
        PipelineTrainer(cfg, TrainConfig(batch_size=5, seq_len=32),
                        _pp_mesh(2), num_microbatches=2)
