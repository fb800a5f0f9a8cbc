"""Serving from published pretrained checkpoints.

The reference's tiers serve real pretrained models via Ollama
(src/devices/nano_api.py:15-16); round 1 here served random weights, so
/chat replies were byte soup.  checkpoints/<preset> (committed, trained by
training/pretrain.py on the synthetic corpus) closes that: these tests
assert the artifacts load, the served text is deterministic NON-GARBAGE,
and the default serving cluster actually picks the weights up.
"""

import dataclasses

import numpy as np
import pytest

from distributed_llm_tpu.config import (default_checkpoint, tiny_cluster,
                                        with_default_checkpoints)
from distributed_llm_tpu.engine.inference import InferenceEngine
from distributed_llm_tpu.training.data import _WORDS

CKPT = default_checkpoint("nano_test")
pytestmark = pytest.mark.skipif(
    CKPT is None, reason="checkpoints/nano_test not published")

# Corpus vocabulary: content words + the template glue words
# (training/data.py _TEMPLATES).
VOCAB = set(_WORDS) | {"when", "the", "a", "is", "runs", "waits", "for",
                       "faster", "than", "because", "of", "ask", "about",
                       "and"}


def _tier(**kw):
    base = dataclasses.replace(tiny_cluster().nano, checkpoint_path=CKPT,
                               max_new_tokens=48)
    return dataclasses.replace(base, **kw)


def test_checkpoint_text_is_deterministic_across_seeds():
    """Engine seed must not matter once weights come from the checkpoint
    (greedy decode): the reply is a function of the artifact."""
    a = InferenceEngine(_tier(), seed=1).generate("user: ask the chip")
    b = InferenceEngine(_tier(), seed=2024).generate("user: ask the chip")
    assert a.text == b.text
    assert a.gen_tokens >= 4


def test_checkpoint_text_is_non_garbage():
    """Served text is structured corpus-like English: printable ASCII and
    mostly words the training distribution contains — not random bytes
    (the round-1 failure mode)."""
    res = InferenceEngine(_tier(), seed=0).generate(
        "user: ask the chip about the mesh")
    text = res.text
    assert text and all(31 < ord(c) < 127 for c in text), repr(text)
    words = [w.strip(".,?!:") for w in text.split()]
    words = [w for w in words if w]
    assert words, repr(text)
    hits = sum(w in VOCAB for w in words)
    # Byte-level decoding can splice novel word fragments; structure, not
    # perfection, is the bar.
    assert hits / len(words) >= 0.4, (text, hits, len(words))


def test_trained_weights_beat_random_on_corpus_nll():
    """The strongest non-garbage signal: the checkpoint's next-byte NLL on
    held-out synthetic text must crush random init's."""
    import jax
    from distributed_llm_tpu import models
    from distributed_llm_tpu.training.data import batches
    from distributed_llm_tpu.training.trainer import lm_loss
    from distributed_llm_tpu.utils.checkpoint import load_params_for_tier

    tier = _tier()
    cfg = tier.model()
    trained = load_params_for_tier(CKPT, cfg)
    random_p = jax.jit(lambda: models.init_params(cfg, seed=7))()
    from distributed_llm_tpu.engine.tokenizer import get_tokenizer
    toks, mask = next(batches(8, 128, seed=31337,      # unseen eval seed
                              tokenizer=get_tokenizer(cfg)))
    nll_t = float(lm_loss(cfg, trained, toks, mask, remat=False))
    nll_r = float(lm_loss(cfg, random_p, toks, mask, remat=False))
    assert nll_t < nll_r / 3, (nll_t, nll_r)
    assert np.isfinite(nll_t)


def test_default_cluster_serves_published_weights():
    """with_default_checkpoints wires the artifacts into the default
    serving/bench cluster (explicit paths and remote tiers untouched)."""
    filled = with_default_checkpoints(tiny_cluster())
    assert filled.nano.checkpoint_path == CKPT
    if default_checkpoint("orin_test"):
        assert filled.orin.checkpoint_path == default_checkpoint("orin_test")
    pinned = dataclasses.replace(tiny_cluster().nano, checkpoint_path="/x")
    keep = with_default_checkpoints(
        dataclasses.replace(tiny_cluster(), nano=pinned))
    assert keep.nano.checkpoint_path == "/x"


def test_batching_engine_serves_checkpoint():
    """The continuous-batching engine path loads the same artifact (the
    EngineManager passes params through for decode_batch > 1 tiers)."""
    from distributed_llm_tpu.engine.manager import EngineManager
    tier = _tier(decode_batch=2)
    mgr = EngineManager(tier, warmup_on_start=False)
    try:
        mgr.start_server()
        seq = InferenceEngine(_tier(), seed=5).generate(
            "user: ask the chip", max_new_tokens=8)
        bat = mgr.engine().generate("user: ask the chip", max_new_tokens=8)
        assert bat.token_ids == seq.token_ids
    finally:
        mgr.stop_server()
