"""Trained semantic encoder + hybrid embedding space
(routing/encoder.py, routing/embedder.py HybridEmbedder): the in-repo
MiniLM stand-in for the semantic strategy and cache.

The decisive capability: a paraphrase with (near-)disjoint wording must
hit the semantic cache under the shipped (hybrid) embedder and MISS
under the hashed n-gram embedder — lexical overlap is exactly what
hashing ranks and what paraphrases lack."""

import numpy as np
import pytest

from distributed_llm_tpu.config import PRODUCTION_CFG
from distributed_llm_tpu.routing.embedder import (HashedNgramEmbedder,
                                                  HybridEmbedder,
                                                  get_embedder)
from distributed_llm_tpu.routing.encoder import (TrainedEncoder,
                                                 encoder_available)
from distributed_llm_tpu.routing.engine import QueryRouter

pytestmark = pytest.mark.skipif(
    not encoder_available(), reason="no encoder weights artifact committed")

# A held-out-group paraphrase pair with almost no shared content words
# (encoder_data.py group 1 forms) and an unrelated pair.
PARA_A = "what is the population of france?"
PARA_B = "how big is france in terms of inhabitants?"
UNRELATED = "write a hello world program in rust"


def _shipped_embedder():
    return get_embedder(PRODUCTION_CFG["embedding_model"])


def test_encoder_unit_norm_and_deterministic():
    enc = TrainedEncoder()
    a1 = enc.encode([PARA_A])[0]
    a2 = enc.encode([PARA_A])[0]
    np.testing.assert_allclose(a1, a2, rtol=1e-5)
    assert np.linalg.norm(a1) == pytest.approx(1.0, abs=1e-3)
    hyb = _shipped_embedder()
    h1 = hyb.encode([PARA_A])[0]
    assert np.linalg.norm(h1) == pytest.approx(1.0, abs=1e-3)


def test_hybrid_beats_hashing_on_disjoint_paraphrase():
    """The capability gap itself: the shipped embedder scores the
    paraphrase above its calibrated cache threshold, hashing scores it
    below ITS calibrated threshold (0.40) — and both keep unrelated
    pairs low."""
    hyb, hashed = _shipped_embedder(), HashedNgramEmbedder()
    assert isinstance(hyb, HybridEmbedder)
    thr = float(PRODUCTION_CFG["cache_similarity_threshold"])

    def sim(emb, a, b):
        za, zb = np.array(emb.encode([a, b]))
        return float(np.dot(za, zb)
                     / (np.linalg.norm(za) * np.linalg.norm(zb) + 1e-9))

    assert sim(hyb, PARA_A, PARA_B) >= thr
    assert sim(hashed, PARA_A, PARA_B) < 0.40     # the r1-r3 calibration
    assert sim(hyb, PARA_A, UNRELATED) < thr
    assert sim(hashed, PARA_A, UNRELATED) < 0.40


def test_paraphrase_cache_hit_with_hybrid_miss_with_hashing():
    """End to end through QueryRouter: the second wording hits the
    semantic cache under the shipped hybrid embedder and misses under
    hashed n-grams (each at its own calibrated threshold)."""
    cfg_enc = dict(PRODUCTION_CFG)
    qr = QueryRouter("hybrid", cfg_enc)
    assert isinstance(qr.cache_embedder, HybridEmbedder)
    qr.route_query(PARA_A, context_key="para")
    d = qr.route_query(PARA_B, context_key="para")
    assert d.cache_hit, d.reasoning

    cfg_hash = dict(PRODUCTION_CFG)
    cfg_hash["embedding_model"] = "hashed-ngram-384"
    cfg_hash["cache_similarity_threshold"] = 0.40
    qr2 = QueryRouter("hybrid", cfg_hash)
    assert isinstance(qr2.cache_embedder, HashedNgramEmbedder)
    qr2.route_query(PARA_A, context_key="para")
    d2 = qr2.route_query(PARA_B, context_key="para")
    assert not d2.cache_hit, d2.reasoning


def test_get_embedder_falls_back_without_artifact(monkeypatch):
    import distributed_llm_tpu.routing.encoder as enc_mod
    monkeypatch.setattr(enc_mod, "encoder_available", lambda *a: False)
    monkeypatch.setattr(enc_mod, "_default", None)
    for name in ("trained-encoder-v1", "hybrid-lexsem-v1"):
        emb = get_embedder(name)
        assert isinstance(emb, HashedNgramEmbedder)


def test_semantic_routing_accuracy_not_regressed():
    """Centroid routing over ALL THREE bench query sets must be at least as
    accurate with the encoder (+ its calibrated thresholds) as with the
    r3 hashed embedder (+ its thresholds)."""
    from distributed_llm_tpu.bench.query_sets import query_sets
    from distributed_llm_tpu.routing.strategies import SemanticStrategy

    queries = [i for qs in query_sets.values() for i in qs]

    def accuracy(cfg):
        strat = SemanticStrategy(
            cfg, embedder=get_embedder(cfg.get("embedding_model")))
        ok = sum(strat.route(i["query"]).device == i["expected_device"]
                 for i in queries)
        return ok / len(queries)

    acc_enc = accuracy(dict(PRODUCTION_CFG))
    acc_hash = accuracy({**PRODUCTION_CFG,
                         "embedding_model": "hashed-ngram-384",
                         "semantic_min_similarity": 0.05})
    assert acc_enc >= acc_hash, (acc_enc, acc_hash)


def test_cache_survives_cross_embedder_persistence(tmp_path):
    """A cache file persisted under one embedding_model must not crash a
    session running another (dims differ): stale-dim entries are simply
    skipped by the semantic scan."""
    cfg_hash = dict(PRODUCTION_CFG)
    cfg_hash["embedding_model"] = "hashed-ngram-384"
    qr = QueryRouter("hybrid", cfg_hash)
    qr.route_query(PARA_A, context_key="x")
    path = str(tmp_path / "cache.json")
    qr.save_cache(path)

    qr2 = QueryRouter("hybrid", dict(PRODUCTION_CFG))
    qr2.load_cache(path)
    d = qr2.route_query(PARA_B, context_key="x")   # must not raise
    assert d.device in ("nano", "orin")


def test_offgen_eval_artifact_in_sync_and_honest():
    """The off-generator generalization eval: the
    committed artifact must match a live re-run (same pairs, same
    embedders), and its headline finding — NO shipped embedder
    generalizes to hand-written off-domain pairs the way MiniLM would
    (AUC well below 0.7 on the adversarial suite) — is pinned here so
    any future encoder that fixes it must also update the artifact and
    the documented drift."""
    import json
    import os

    from distributed_llm_tpu.routing.encoder_eval import load_pairs, run_eval

    pos, neg = load_pairs()
    assert len(pos) >= 50 and len(neg) >= 50
    live = run_eval()
    art_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "distributed_llm_tpu", "routing",
        "offgen_eval.json")
    with open(art_path) as f:
        committed = json.load(f)
    for emb in ("hashed", "encoder", "hybrid"):
        assert emb in committed and emb in live, emb
        for key in ("auc", "pos_mean", "neg_mean", "hit_rate_paraphrase",
                    "false_hit_rate_unrelated"):
            assert committed[emb][key] == pytest.approx(
                live[emb][key], abs=1e-6), (emb, key)
    # The honest negative result (documented in PARITY.md): off-generator
    # semantics remain the gap vs the reference's MiniLM.  The hybrid
    # still ranks above pure hashing on this suite.
    assert live["hybrid"]["auc"] < 0.7
    assert live["hybrid"]["auc"] > live["hashed"]["auc"]
