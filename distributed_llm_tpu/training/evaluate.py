"""Held-out answer-quality evaluation for serving tiers.

The reference's routing premise is a CAPABILITY asymmetry: orin serves a
strictly stronger model than nano (llama3-8B vs phi3-mini,
src/devices/orin_api.py:17-18 vs nano_api.py:15-21), so routing a complex
query up buys real answer quality at higher cost.  This framework trains
its own tier checkpoints (training/pretrain.py), so that premise must be
*measured*, not asserted: this module scores each tier's checkpoint on a
held-out slice of the training distribution — per-token cross-entropy
(the LM's answer-quality proxy) and next-token top-1 accuracy — with the
SAME token stream for every tier, so numbers are directly comparable.

Orin should win quality while costing more per token (ms/token), which is
what makes every routing strategy's capability-vs-cost trade falsifiable
in-repo.

Held-out means a generator seed disjoint from every training seed:
pretrain.py draws batches(seed=tc.seed) with small seeds (0 by default);
the eval stream uses HELDOUT_SEED, far outside that range, so no eval row
was ever a training row (the corpus is generated, not downloaded —
train/test separation is by seed).
"""

from __future__ import annotations

import argparse
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import MODEL_PRESETS, ModelConfig

HELDOUT_SEED = 773_001  # disjoint from training seeds (pretrain uses ~0-10)


def heldout_batches(batch_size: int, seq_len: int, tokenizer,
                    seed: int = HELDOUT_SEED
                    ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """The training mix (chat + sentence pseudo-text, training/data.py)
    drawn from a held-out seed."""
    from .data import batches
    return batches(batch_size, seq_len, seed=seed, tokenizer=tokenizer)


def _eval_fn(cfg: ModelConfig):
    """Jitted (loss, top-1 next-token accuracy) over one batch."""
    from ..models import model_module
    from ..models import transformer

    def run(params, tokens, loss_mask):
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        out = model_module(cfg).prefill(cfg, params, tokens, positions)
        hidden = out[0]
        logits = transformer.logits_from_hidden(params, hidden[:, :-1])
        targets = tokens[:, 1:]
        mask = loss_mask[:, 1:].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll * mask) / denom
        acc = jnp.sum((jnp.argmax(logp, axis=-1) == targets) * mask) / denom
        return loss, acc

    return jax.jit(run)


def eval_quality(cfg: ModelConfig, params: Any, *,
                 n_batches: int = 4, batch_size: int = 8,
                 seq_len: Optional[int] = None,
                 seed: int = HELDOUT_SEED) -> Dict[str, float]:
    """Mean held-out per-token loss / perplexity / next-token accuracy
    for ``params`` under ``cfg``.  Deterministic in (cfg, params, seed):
    every tier sees the identical token stream."""
    from ..engine.tokenizer import get_tokenizer
    seq = seq_len or min(256, cfg.max_seq_len)
    run = _eval_fn(cfg)
    data = heldout_batches(batch_size, seq, get_tokenizer(cfg), seed=seed)
    losses, accs = [], []
    for _, (toks, mask) in zip(range(n_batches), data):
        loss, acc = run(params, jnp.asarray(toks), jnp.asarray(mask))
        losses.append(float(loss))
        accs.append(float(acc))
    mean_loss = float(np.mean(losses))
    return {
        "eval_loss": round(mean_loss, 4),
        "perplexity": round(float(np.exp(mean_loss)), 3),
        "next_token_acc": round(float(np.mean(accs)), 4),
        "n_tokens": n_batches * batch_size * (seq - 1),
    }


def eval_checkpoint(preset: str, checkpoint_path: str,
                    **kw) -> Dict[str, float]:
    """Load a serving checkpoint's params (bf16, host-local) and score
    them; the tiers serve these same artifacts via
    TierConfig.checkpoint_path."""
    from ..utils.checkpoint import load_params_for_tier
    cfg = MODEL_PRESETS[preset]
    params = load_params_for_tier(checkpoint_path, cfg)
    return eval_quality(cfg, params, **kw)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preset", required=True, choices=sorted(MODEL_PRESETS))
    ap.add_argument("--checkpoint", required=True)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="pin jax to host CPU")
    args = ap.parse_args(argv)
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    out = eval_checkpoint(args.preset, args.checkpoint,
                          n_batches=args.batches,
                          batch_size=args.batch_size, seq_len=args.seq_len)
    import json
    print(json.dumps({"preset": args.preset, **out}))


if __name__ == "__main__":
    main()
