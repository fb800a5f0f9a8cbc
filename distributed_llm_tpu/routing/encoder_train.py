"""Contrastive training for the semantic encoder (routing/encoder.py).

In-batch-negative NT-Xent: a batch of (anchor, positive) paraphrase
pairs is encoded into unit vectors A, P; logits = A·Pᵀ/τ and the target
is the diagonal — every other pair in the batch serves as a negative.
Symmetrized (anchor→positive and positive→anchor).

Training data is the generated paraphrase corpus
(routing/encoder_data.py); evaluation is held-out template GROUPS
(meanings never seen in training) plus unrelated cross-group pairs, and
the reported calibration is the positive/negative score separation the
cache threshold rides on (config "cache_similarity_threshold" for
embedding_model="trained-encoder-v1").

Run:  python -m distributed_llm_tpu.routing.encoder_train \
          --out distributed_llm_tpu/routing/encoder_weights.npz
(CPU-friendly: ~1.3M params, a few minutes for 600 steps.)
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Tuple

import numpy as np

from .encoder import (ENCODER_DIM, MAX_TOKENS, encode_fn,
                      init_encoder_params)
from .encoder_data import contrastive_pairs, unrelated_pairs


def _tokenize_pairs(pairs: List[Tuple[str, str]]):
    from ..engine.bpe import load_default
    tok = load_default()

    def toks(texts):
        ids = np.zeros((len(texts), MAX_TOKENS), np.int32)
        mask = np.zeros((len(texts), MAX_TOKENS), np.float32)
        for r, text in enumerate(texts):
            enc = tok.encode(text.lower())[:MAX_TOKENS]
            ids[r, :len(enc)] = enc
            mask[r, :len(enc)] = 1.0
        return ids, mask

    a_ids, a_mask = toks([p[0] for p in pairs])
    b_ids, b_mask = toks([p[1] for p in pairs])
    return a_ids, a_mask, b_ids, b_mask


def _tokenize_labels():
    """semantic_labels.json texts + class ids (nano=0, orin=1) — the
    centroid-classification aux batch."""
    import json
    import os

    from ..engine.bpe import load_default
    tok = load_default()
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "bench", "semantic_labels.json")
    with open(path) as f:
        rows = json.load(f)
    ids = np.zeros((len(rows), MAX_TOKENS), np.int32)
    mask = np.zeros((len(rows), MAX_TOKENS), np.float32)
    y = np.zeros(len(rows), np.int32)
    for r, row in enumerate(rows):
        enc = tok.encode(row["text"].lower())[:MAX_TOKENS]
        ids[r, :len(enc)] = enc
        mask[r, :len(enc)] = 1.0
        y[r] = 1 if row["label"] == "orin" else 0
    return ids, mask, y


def train(out: str, *, steps: int = 600, batch_size: int = 64,
          lr: float = 3e-3, temperature: float = 0.08,
          class_weight: float = 0.3, seed: int = 0,
          log=print) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    import optax

    pairs = contrastive_pairs("train", seed=seed)
    log(f"[encoder] {len(pairs)} training pairs")
    a_ids, a_mask, b_ids, b_mask = _tokenize_pairs(pairs)
    l_ids, l_mask, l_y = _tokenize_labels()

    params = init_encoder_params(seed=seed)
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps=max(steps // 10, 1), decay_steps=steps)
    opt = optax.adamw(sched, weight_decay=1e-4)
    opt_state = opt.init(params)

    def loss_fn(p, ai, am, bi, bm, li, lm, ly):
        # MEANING head: in-batch-negative NT-Xent on paraphrase pairs —
        # the cache's similarity space.
        za = encode_fn(p, ai, am, head="meaning")     # [B, d] unit
        zb = encode_fn(p, bi, bm, head="meaning")
        logits = za @ zb.T / temperature              # [B, B]
        labels = jnp.arange(logits.shape[0])
        l1 = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        l2 = optax.softmax_cross_entropy_with_integer_labels(logits.T, labels)
        ntxent = jnp.mean(l1 + l2) / 2.0
        # CLASS head: centroid-classification on the label texts — the
        # semantic STRATEGY classifies a query by cosine to per-class
        # centroids of these exact texts (strategies.py), so optimize
        # that readout directly.  A separate head because the two
        # objectives fight in one projection (encoder.py docstring):
        # measured at weight 0.3 on a shared head, this term collapsed
        # held-out paraphrase similarity 0.25 → 0.11.
        zl = encode_fn(p, li, lm, head="class")       # [L, d] unit
        w_orin = ly.astype(jnp.float32)
        w_nano = 1.0 - w_orin
        cn = jnp.sum(zl * w_nano[:, None], 0) / jnp.maximum(w_nano.sum(), 1)
        co = jnp.sum(zl * w_orin[:, None], 0) / jnp.maximum(w_orin.sum(), 1)
        cn = cn / jnp.maximum(jnp.linalg.norm(cn), 1e-9)
        co = co / jnp.maximum(jnp.linalg.norm(co), 1e-9)
        cls_logits = jnp.stack([zl @ cn, zl @ co], axis=1) / 0.1
        cls = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            cls_logits, ly))
        return ntxent + class_weight * cls

    @jax.jit
    def step(p, s, ai, am, bi, bm):
        loss, grads = jax.value_and_grad(loss_fn)(
            p, ai, am, bi, bm, l_ids, l_mask, l_y)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    rng = np.random.default_rng(seed)
    n = len(pairs)
    t0 = time.perf_counter()
    loss = float("nan")
    for i in range(1, steps + 1):
        idx = rng.choice(n, size=min(batch_size, n), replace=False)
        params, opt_state, loss = step(
            params, opt_state, a_ids[idx], a_mask[idx],
            b_ids[idx], b_mask[idx])
        if i % 50 == 0 or i == 1:
            log(f"[encoder] step {i}/{steps} loss={float(loss):.4f} "
                f"({i / (time.perf_counter() - t0):.1f} steps/s)")

    params = jax.device_get(params)
    # fp16 artifact: half the bytes, fp32-restored at load.
    np.savez_compressed(out, **{k: np.asarray(v, np.float16)
                                for k, v in params.items()})
    log(f"[encoder] saved {out}")
    metrics = evaluate(out, log=log)
    metrics["final_train_loss"] = round(float(loss), 4)
    return metrics


def evaluate(weights_path: str, log=print) -> Dict[str, float]:
    """Held-out paraphrase vs unrelated separation for the committed
    artifact AND the hashed fallback (the capability gap the encoder
    exists to close)."""
    from .embedder import HashedNgramEmbedder
    from .encoder import TrainedEncoder

    held = contrastive_pairs("heldout", seed=123)
    unrel = unrelated_pairs(n=min(300, 4 * len(held)), seed=123)

    def sims(embedder, pairs):
        za = embedder.encode([p[0] for p in pairs])
        zb = embedder.encode([p[1] for p in pairs])
        za = za / np.maximum(np.linalg.norm(za, axis=1, keepdims=True), 1e-9)
        zb = zb / np.maximum(np.linalg.norm(zb, axis=1, keepdims=True), 1e-9)
        return np.sum(za * zb, axis=1)

    out: Dict[str, float] = {"heldout_pairs": len(held),
                             "unrelated_pairs": len(unrel)}
    for name, emb in (("encoder", TrainedEncoder(weights_path)),
                      ("hashed", HashedNgramEmbedder())):
        pos, neg = sims(emb, held), sims(emb, unrel)
        # The threshold that best separates positives from negatives,
        # and each side's error at that threshold.
        grid = np.linspace(0.0, 1.0, 201)
        acc = [(np.mean(pos >= t) + np.mean(neg < t)) / 2.0 for t in grid]
        best = int(np.argmax(acc))
        out.update({
            f"{name}_pos_mean": round(float(np.mean(pos)), 4),
            f"{name}_neg_mean": round(float(np.mean(neg)), 4),
            f"{name}_sep_acc": round(float(acc[best]), 4),
            f"{name}_best_threshold": round(float(grid[best]), 3),
            f"{name}_pos_p10": round(float(np.percentile(pos, 10)), 4),
            f"{name}_neg_p90": round(float(np.percentile(neg, 90)), 4),
        })
        log(f"[encoder] {name}: pos={out[f'{name}_pos_mean']} "
            f"neg={out[f'{name}_neg_mean']} "
            f"sep_acc={out[f'{name}_sep_acc']} "
            f"@thr={out[f'{name}_best_threshold']}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None,
                    help="npz path (default: the committed artifact)")
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--temperature", type=float, default=0.08)
    ap.add_argument("--class-weight", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-only", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="pin jax to host CPU")
    args = ap.parse_args(argv)
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    from .encoder import _DEFAULT_WEIGHTS
    out = args.out or _DEFAULT_WEIGHTS
    if args.eval_only:
        print(json.dumps(evaluate(out)))
        return
    metrics = train(out, steps=args.steps, batch_size=args.batch_size,
                    lr=args.lr, temperature=args.temperature,
                    class_weight=args.class_weight, seed=args.seed)
    print(json.dumps(metrics))


if __name__ == "__main__":
    main()
