"""Pretrain a model preset on the synthetic corpus to a loss plateau and
publish a serving checkpoint.

The reference never trains anything — its tiers serve Ollama-pulled
pretrained models (src/devices/nano_api.py:15-16, orin_api.py:17-18).
Zero egress means no downloadable weights here, so the framework makes its
own: the byte-level LM learns the synthetic template corpus
(training/data.py) to a plateau, the train state is checkpointed with the
preemption-safe versioned layout (utils/checkpoint.py), and serving tiers
pick the artifact up via ``TierConfig.checkpoint_path`` — after which
``/chat`` replies are deterministic structured text, not random bytes.

Run:  python -m distributed_llm_tpu.training.pretrain \
          --preset nano_test --out checkpoints/nano_test
"""

from __future__ import annotations

import argparse
import collections
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from ..config import MODEL_PRESETS
from .data import batches
from .trainer import TrainConfig, Trainer


def pretrain(preset: str, out: str, *,
             batch_size: int = 16,
             seq_len: Optional[int] = None,
             max_steps: int = 2000,
             eval_every: int = 25,
             patience: int = 4,
             min_delta: float = 0.02,
             learning_rate: float = 1e-3,
             seed: int = 0,
             save_every: Optional[int] = None,
             resume: bool = False,
             log: Callable[[str], None] = print) -> Dict[str, float]:
    """Train ``preset`` until the eval-window mean loss stops improving by
    ``min_delta`` for ``patience`` consecutive windows (or ``max_steps``),
    then checkpoint to ``out``.  ``save_every`` > 0 additionally
    checkpoints mid-run — a preemption leaves a resumable ``latest``.
    ``resume`` continues from an existing checkpoint at ``out`` (params +
    optimizer state + step counter); ``max_steps`` counts ADDITIONAL
    steps.  The resumed run draws from a fresh generator stream offset by
    the saved step count — disjoint from the original run's batches at
    ANY (batch_size, seq_len), so changing the batch shape on resume
    neither repeats nor skips training text.

    Data parallelism uses every local device that divides the batch
    (single device otherwise); the model families' own sharding rules
    handle anything bigger.
    """
    import os
    cfg = MODEL_PRESETS[preset]
    seq = seq_len or min(256, cfg.max_seq_len)
    devs = jax.devices()
    dp = next(d for d in range(len(devs), 0, -1) if batch_size % d == 0)
    mesh = jax.sharding.Mesh(np.asarray(devs[:dp]), ("dp",))
    trainer = Trainer(cfg, TrainConfig(batch_size=batch_size, seq_len=seq,
                                       learning_rate=learning_rate,
                                       warmup_steps=min(50, max_steps // 4),
                                       decay_steps=max(1000, max_steps),
                                       seed=seed), mesh)
    resumed_from = 0
    if resume:
        if os.path.isdir(out):
            trainer.load(out)
            resumed_from = trainer.step_count
            # The restored optimizer count may sit at/past the fresh
            # schedule's cosine horizon, where LR is pinned to the floor
            # and the extension run cannot move the checkpoint.  Stretch
            # the horizon so this run decays over ITS steps instead.
            if trainer.extend_schedule(resumed_from + max_steps):
                log(f"[pretrain] extended LR schedule to "
                    f"{resumed_from + max_steps} steps")
            log(f"[pretrain] resumed {preset} from {out} at step "
                f"{resumed_from}")
        else:
            log(f"[pretrain] WARNING: --resume but no checkpoint at "
                f"{out} — training from scratch")
    log(f"[pretrain] {preset}: {cfg.num_layers}L/{cfg.hidden_size}h "
        f"({cfg.param_count()/1e6:.2f}M params) batch={batch_size} "
        f"seq={seq} dp={dp} max_steps={max_steps}")

    window: collections.deque = collections.deque(maxlen=eval_every)
    best = float("inf")
    stale = 0
    t0 = time.perf_counter()
    final = float("nan")
    from ..engine.tokenizer import get_tokenizer
    # A resumed run offsets the generator seed by the saved step count:
    # batches() derives each batch's rng from (seed << 20) ^ step, so a
    # different seed yields a disjoint stream regardless of batch shape.
    data_seed = seed + resumed_from
    data = batches(batch_size, seq, seed=data_seed,
                   tokenizer=get_tokenizer(cfg))
    for step, (toks, mask) in enumerate(data, start=1):
        metrics = trainer.train_step(toks, mask)
        window.append(metrics["loss"])
        if step % eval_every == 0:
            mean = float(np.mean(window))
            final = mean
            log(f"[pretrain] step {step}: loss={mean:.4f} "
                f"(best={best:.4f}, {step / (time.perf_counter()-t0):.1f} "
                f"steps/s)")
            if best - mean < min_delta:
                stale += 1
                if stale >= patience:
                    log(f"[pretrain] plateau after {step} steps")
                    break
            else:
                stale = 0
            best = min(best, mean)
        if save_every and step % save_every == 0:
            trainer.save(out)
        if step >= max_steps:
            break
    # None = the loop's save_every save already published this exact
    # step (save skipped, state identical) — report the root it lives at.
    path = trainer.save(out) or out
    log(f"[pretrain] saved {path} at step {trainer.step_count} "
        f"(loss={final:.4f})")
    return {"steps": trainer.step_count, "final_loss": final,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preset", required=True, choices=sorted(MODEL_PRESETS))
    ap.add_argument("--out", required=True)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--max-steps", type=int, default=2000)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--patience", type=int, default=4)
    ap.add_argument("--min-delta", type=float, default=0.02)
    ap.add_argument("--learning-rate", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save-every", type=int, default=None)
    ap.add_argument("--resume", action="store_true",
                    help="continue from an existing checkpoint at --out "
                         "(max-steps counts additional steps)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin jax to host CPU")
    args = ap.parse_args(argv)
    from ..utils.compile_cache import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    pretrain(args.preset, args.out, batch_size=args.batch_size,
             seq_len=args.seq_len, max_steps=args.max_steps,
             eval_every=args.eval_every, patience=args.patience,
             min_delta=args.min_delta, learning_rate=args.learning_rate,
             seed=args.seed, save_every=args.save_every, resume=args.resume)


if __name__ == "__main__":
    main()
