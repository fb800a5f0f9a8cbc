"""training/pretrain.py: plateau detection, mid-run checkpointing, and
the published-artifact layout serving reads."""

import jax

import pytest

from distributed_llm_tpu.training import pretrain as pt


def test_pretrain_plateaus_and_publishes(tmp_path):
    out = tmp_path / "ck"
    res = pt.pretrain("nano_test", str(out), batch_size=4, seq_len=32,
                      max_steps=60, eval_every=10, patience=2,
                      min_delta=10.0,          # huge delta => early plateau
                      log=lambda *_: None)
    # Plateau must trigger well before max_steps with an unmeetable delta.
    assert res["steps"] < 60
    assert (out / "latest").is_symlink()
    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.utils.checkpoint import load_params_for_tier
    params = load_params_for_tier(str(out), MODEL_PRESETS["nano_test"])
    assert "embed" in params


def test_pretrain_save_every_leaves_resumable_latest(tmp_path):
    out = tmp_path / "ck"
    pt.pretrain("nano_test", str(out), batch_size=4, seq_len=32,
                max_steps=10, eval_every=50, save_every=5,
                log=lambda *_: None)
    # v5 (mid-run), v10 (final); prune keeps the newest two.
    versions = sorted(d.name for d in out.iterdir() if d.name.startswith("v"))
    assert versions == ["v10", "v5"], versions
    # The artifact resumes into a Trainer (cross-run restore path).
    import numpy as np
    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.training.trainer import TrainConfig, Trainer
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    tr = Trainer(MODEL_PRESETS["nano_test"],
                 TrainConfig(batch_size=4, seq_len=32), mesh)
    tr.load(str(out))
    assert tr.step_count == 10


def test_pretrain_resume_continues_from_checkpoint(tmp_path):
    """--resume loads params + optimizer + step counter and counts
    max_steps as ADDITIONAL steps; the data stream skips past the saved
    position so no batch repeats."""
    out = tmp_path / "ck"
    pt.pretrain("nano_test", str(out), batch_size=4, seq_len=32,
                max_steps=8, eval_every=50, log=lambda *_: None)
    res = pt.pretrain("nano_test", str(out), batch_size=4, seq_len=32,
                      max_steps=5, eval_every=50, resume=True,
                      log=lambda *_: None)
    assert res["steps"] == 13          # 8 saved + 5 additional


def test_resume_extends_lr_schedule_past_horizon(tmp_path):
    """A resume whose restored step counter sits at/past the cosine
    horizon must NOT train at the schedule floor: pretrain stretches the
    horizon to resumed_from + max_steps so the extension run decays over
    its own steps (the quality-gate extensions were once 0-LR
    no-ops)."""
    import numpy as np

    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.training.trainer import (
        TrainConfig, Trainer, make_optimizer, schedule_horizon)

    # Unit level: extend_schedule grows the horizon and keeps state.
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    tr = Trainer(MODEL_PRESETS["nano_test"],
                 TrainConfig(batch_size=4, seq_len=32, warmup_steps=2),
                 mesh)
    assert schedule_horizon(tr.tc) == 1000
    old_state = tr.opt_state
    assert tr.extend_schedule(1800)
    assert schedule_horizon(tr.tc) == 1800
    # Optimizer state (moments + count) carries over untouched.
    assert jax.tree.structure(tr.opt_state) == jax.tree.structure(old_state)
    assert not tr.extend_schedule(1700)          # never shrinks

    # Schedule level: at step 1000 the OLD horizon pinned LR to the
    # floor; the stretched horizon keeps a mid-cosine LR well above it.
    tc = TrainConfig(warmup_steps=50, learning_rate=1e-3)
    import optax
    old_sched = optax.warmup_cosine_decay_schedule(
        0.0, 1e-3, 50, schedule_horizon(tc), end_value=1e-4)
    new_sched = optax.warmup_cosine_decay_schedule(
        0.0, 1e-3, 50, 1800, end_value=1e-4)
    assert float(old_sched(1000)) == pytest.approx(1e-4)
    assert float(new_sched(1000)) > 3e-4

    # End-to-end: a resumed pretrain past the horizon logs the extension
    # and still advances the checkpoint.
    out = tmp_path / "ck"
    pt.pretrain("nano_test", str(out), batch_size=4, seq_len=32,
                max_steps=6, eval_every=50, log=lambda *_: None)
    logs = []
    # max_steps=1200 drives the horizon math (6 + 1200 > 1000) but the
    # unmeetable min_delta plateaus the run after ~2 eval windows.
    pt.pretrain("nano_test", str(out), batch_size=4, seq_len=32,
                max_steps=1200, eval_every=5, patience=1,
                min_delta=1000.0, resume=True, log=logs.append)
    assert any("extended LR schedule to 1206" in line for line in logs)


def test_heldout_eval_deterministic_and_seed_disjoint(tmp_path):
    """Same (cfg, params, seed) -> identical numbers; the held-out stream
    differs from the training stream (seed separation is the train/test
    split for a generated corpus)."""
    import numpy as np

    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.engine.tokenizer import get_tokenizer
    from distributed_llm_tpu.training import evaluate as ev

    cfg = MODEL_PRESETS["nano_test"]
    tok = get_tokenizer(cfg)
    held = next(iter(ev.heldout_batches(2, 64, tok)))[0]
    train = next(iter(__import__(
        "distributed_llm_tpu.training.data", fromlist=["batches"]
    ).batches(2, 64, seed=0, tokenizer=tok)))[0]
    assert not np.array_equal(held, train)

    from distributed_llm_tpu.utils.checkpoint import load_params_for_tier
    params = load_params_for_tier("checkpoints/nano_test", cfg)
    a = ev.eval_quality(cfg, params, n_batches=1, batch_size=2, seq_len=64)
    b = ev.eval_quality(cfg, params, n_batches=1, batch_size=2, seq_len=64)
    assert a == b
    assert 0.0 < a["eval_loss"] < 10.0
    assert 0.0 <= a["next_token_acc"] <= 1.0


def test_tier_quality_asymmetry_on_committed_checkpoints():
    """The routing premise, measured: the bigger
    orin_test checkpoint beats nano_test on held-out per-token loss over
    the identical token stream."""
    from distributed_llm_tpu.config import MODEL_PRESETS
    from distributed_llm_tpu.training.evaluate import eval_checkpoint

    nano = eval_checkpoint("nano_test", "checkpoints/nano_test",
                           n_batches=2, batch_size=4)
    orin = eval_checkpoint("orin_test", "checkpoints/orin_test",
                           n_batches=2, batch_size=4)
    assert orin["eval_loss"] < nano["eval_loss"], (nano, orin)
