"""Roofline work accounting (utils/roofline.py) + its engine wiring.

The reference never measures hardware utilization (Ollama hides the
arithmetic, src/devices/nano_api.py:76); here every phase accounts its
FLOPs and HBM bytes.  These tests pin the formulas to hand-computed
values on tiny configs and check both engines actually accumulate work.
"""

import jax
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig, tiny_cluster
from distributed_llm_tpu.utils import roofline


CFG = MODEL_PRESETS["nano_test"]       # h=64, L=2, heads=4, kv=2, ffn=128


def test_active_matmul_params_dense_hand_count():
    h, f, l, v = 64, 128, 2, CFG.vocab_size
    kv = 2 * (64 // 4)                  # kv_heads * head_dim = 32
    attn = h * h + 2 * h * kv + h * h   # q + kv + o
    expected = l * (attn + 3 * h * f) + v * h
    assert roofline.active_matmul_params(CFG) == expected


def test_moe_top2_flops_vs_full_weight_bytes():
    moe = MODEL_PRESETS["moe_test"]     # 4 experts, same dims as nano_test
    # FLOPs: top-2 experts active -> FFN term doubles vs dense.
    dense_ffn = 2 * 3 * 64 * 128        # layers * 3hf
    assert (roofline.active_matmul_params(moe)
            - roofline.active_matmul_params(CFG)) == dense_ffn
    # Bytes: dense-dispatch einsum streams ALL 4 experts.
    delta = roofline.weight_bytes(moe) - roofline.weight_bytes(CFG)
    assert delta == 2 * 3 * 64 * 128 * (4 - 1) * 2   # l*3hf*(E-1)*2B


def test_latent_family_counts_experts_per_token_and_the_shared_one():
    cfg = MODEL_PRESETS["latent_test"]  # 1 dense + 2 expert layers, top-2
    h, e, f = 64, 8, 32                 # of 8 experts + 1 shared, width 32
    attn = (h * 32 + 32 * 4 * (16 + 8) + h * (24 + 8)
            + 24 * 4 * (16 + 16) + 4 * 16 * h)
    per_token = (3 * attn + 3 * h * 128
                 + 2 * ((2 + 1) * 3 * h * f + h * e) + cfg.vocab_size * h)
    assert roofline.active_matmul_params(cfg) == per_token
    # Three experts a token instead of two moves the count by two layers'
    # worth of one expert; nothing assumes top-2.
    import dataclasses
    assert (roofline.active_matmul_params(
        dataclasses.replace(cfg, experts_per_token=3)) - per_token
        == 2 * 3 * h * f)
    # Held: all 8 + the shared one; an untied head beside the embedding.
    body = 3 * attn + 3 * h * 128 + 2 * (8 + 1) * 3 * h * f
    assert roofline.weight_bytes(cfg) == (
        body * 2 + (2 * cfg.vocab_size * h + 7 * h) * 2)
    assert roofline.kv_bytes_per_pos(cfg) == 3 * 32 * 2


def test_shared_kv_family_counts_readers_rings_and_rows_a_step():
    # The hand count of every matrix is in tests/test_shared_kv_hybrid.py;
    # here: what a decode step MOVES beside the weights, for GET /stats.
    cfg = MODEL_PRESETS["shared_kv_test"]       # MWMWMWMFGXGX, window 16
    assert cfg.shared_kv and cfg.hybrid
    # One cached layer (F): K and V of 4 heads x 8, bfloat16 ...
    assert roofline.kv_bytes_per_pos(cfg) == 2 * 4 * 8 * 2
    # ... read by F and the two X layers.
    assert roofline.kv_readers(cfg) == 3
    assert roofline.kv_readers(CFG) == 1
    ring = 3 * 2 * 16 * 32 * 2                  # 3 W layers, K and V
    row = 4 * (128 * 8 * 4 + 3 * 128 * 2)       # 4 M layers, S + tail
    assert roofline.ring_row_bytes(cfg) == ring
    assert roofline.state_row_bytes(cfg) == row
    one = roofline.decode_work(cfg, steps=1, ctx=64, batch=1)
    four = roofline.decode_work(cfg, steps=1, ctx=64, batch=4)
    assert four["hbm_bytes"] - one["hbm_bytes"] == 3 * (
        3 * 128 * 64 + ring + 2 * row)
    # A longer context costs the readers' K/V alone: rings and rows do
    # not grow with the sequence.
    longer = roofline.decode_work(cfg, steps=1, ctx=128, batch=4)
    assert longer["hbm_bytes"] - four["hbm_bytes"] == 4 * 3 * 128 * 64
    # A chunk writes one layer's K/V.
    assert (roofline.prefill_work(cfg, 32, 16)["hbm_bytes"]
            - roofline.weight_bytes(cfg)) == 16 * 128


def test_weight_bytes_int8_halves_body_only():
    bf16 = roofline.weight_bytes(CFG, "none")
    i8 = roofline.weight_bytes(CFG, "int8")
    emb = (CFG.vocab_size * 64 + (2 * 2 + 1) * 64) * 2   # stays bf16
    assert i8 == (bf16 - emb) // 2 + emb


def test_prefill_work_causal_quadratic():
    w = roofline.prefill_work(CFG, 32, 0, wbytes=1000)
    pm = roofline.active_matmul_params(CFG)
    assert w["tokens"] == 32
    assert w["flops"] == pytest.approx(2.0 * pm * 32
                                       + 2.0 * 64 * 2 * 32 * 32)
    assert w["hbm_bytes"] == 1000 + roofline.kv_bytes_per_pos(CFG) * 32
    # A chunk starting at 16 does the quadratic difference, not the square.
    w2 = roofline.prefill_work(CFG, 32, 16, wbytes=0)
    assert w2["flops"] == pytest.approx(2.0 * pm * 16
                                        + 2.0 * 64 * 2 * (32**2 - 16**2))


def test_decode_work_scales_with_batch_and_ctx():
    one = roofline.decode_work(CFG, steps=4, ctx=64, batch=1, wbytes=500)
    two = roofline.decode_work(CFG, steps=4, ctx=64, batch=2, wbytes=500)
    assert two["flops"] == pytest.approx(2 * one["flops"])
    # Weights stream once per step regardless of batch — only KV doubles.
    assert (two["hbm_bytes"] - one["hbm_bytes"]
            == 4 * roofline.kv_bytes_per_pos(CFG) * 64)
    assert one["tokens"] == 4 and two["tokens"] == 8


def test_inference_engine_accumulates_work():
    from distributed_llm_tpu.engine.inference import InferenceEngine
    eng = InferenceEngine(tiny_cluster().nano, seed=0)
    eng.generate("hello roofline", max_new_tokens=4)
    work = eng.phases.work_summary()
    assert work["prefill"]["flops"] > 0
    assert work["prefill"]["seconds"] > 0
    assert work["decode"]["hbm_bytes"] > 0
    assert work["decode"]["tokens"] >= 1


def test_batching_engine_accumulates_work():
    import dataclasses
    from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
    tier = dataclasses.replace(tiny_cluster().nano, decode_batch=2)
    eng = ContinuousBatchingEngine(tier, seed=0)
    try:
        eng.generate("hello batched roofline", max_new_tokens=4)
        work = eng.phases.work_summary()
        assert work["prefill"]["flops"] > 0
        assert work["decode"]["flops"] > 0
    finally:
        eng.stop()


def test_engine_stats_exposes_work_and_zero_free():
    from distributed_llm_tpu.engine.inference import InferenceEngine
    from distributed_llm_tpu.utils.telemetry import engine_stats
    eng = InferenceEngine(tiny_cluster().nano, seed=0)
    eng.generate("stats", max_new_tokens=2)
    entry = engine_stats(eng)
    assert "work" in entry and "prefill" in entry["work"]
    # tokenize/detokenize report no device work.
    assert set(entry["work"]) <= {"prefill", "decode"}
