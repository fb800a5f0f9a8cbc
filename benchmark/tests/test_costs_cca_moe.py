"""families/cca_moe_decoder.py against hand-worked sizes of ZAYA1-8B's
first stage (CPU, by hand: ``python3 -m pytest benchmark/tests -q``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import costs  # noqa: E402
import manifest as mf  # noqa: E402

FAMILY = "cca_moe_decoder"


def model():
    with open(os.path.join(HERE, "configs", "zaya1-8b.json")) as f:
        return json.load(f)


def test_parameters_a_layer():
    fam, m = mf.load_family(FAMILY), model()
    # W_q 2048 x 1024 and W_o 1024 x 2048 = 2 x 2 097 152; W_k 2048 x 256
    # and [W_v1 | W_v2] 2048 x (128 + 128) = 2 x 524 288: 5.24 M.
    assert fam.attention_matrix_params(m) == 5_242_880
    # Depthwise 2 taps x 1280 + its bias 1280; grouped by head 2 taps x 10
    # blocks of 128 x 128 = 327 680 + its bias 1280: 0.33 M.
    assert fam.conv_params(m) == 2 * 1280 + 1280 + 327_680 + 1280 == 332_800
    # W_r 2048 x 256 = 524 288; 256 x 256 twice = 131 072; 256 x 16 = 4096;
    # the carry's gain and the norm's 2 x 256; the bias 16: 0.66 M.
    assert fam.router_params(m) == 524_288 + 131_072 + 4096 + 512 + 16 \
        == 659_984
    # One gated expert 3 x 2048 x 2048 = 12.58 M; sixteen 201.33 M.
    assert fam.expert_params(m) == 12_582_912
    assert 16 * fam.expert_params(m) == 201_326_592
    assert fam.layer_params(m) == 5_242_880 + 332_800 + 659_984 \
        + 201_326_592 == 207_562_256                       # 207.6 M
    # Two pre-norm gains (bf16), a temperature a K/V head and two merges
    # of four vectors (float32).
    assert fam.vector_bytes_per_layer(m) == 2 * 2048 * 2 + 8 + 8 * 2048 * 4


def test_weights_held_by_the_stage():
    fam, m = mf.load_family(FAMILY), model()
    layers = 20 * 207_562_256 * 2
    assert layers == 8_302_490_240                         # 8.30 GB
    table = 262_272 * 2048 * 2
    assert table == 1_074_266_112                          # 1.07 GB, tied
    total = costs.weight_bytes_per_chip(m, family=FAMILY)
    assert total == layers + table + 20 * 73_736 == 9_378_231_072
    assert round(total / 1e9, 2) == 9.38
    # Over half of a 16 GiB chip; all 40 layers would be 17.7 GB.
    assert total / 17.18e9 > 0.5
    assert (layers * 2 + table) / 1e9 > 17.18
    with pytest.raises(ValueError, match="one chip a stage"):
        fam.weight_bytes_per_chip(m, 2)


def test_cache_and_tail():
    fam, m = mf.load_family(FAMILY), model()
    # K (as attended) and V of 2 heads x 128 over 20 layers, 2 B each:
    # 1024 B a layer.
    assert costs.kv_bytes_per_token(m, family=FAMILY) == 20 * 1024 == 20_480
    # A layer: 1280 + 1280 inputs of the two convolutions + 128 shifted
    # values = 2688 numbers, 5376 B.
    assert fam.tail_bytes_per_slot(m) == 20 * 5376 == 107_520
    # 16 slots x 5120 positions in blocks of 64, + the trash block.
    blocks = 16 * 5120 // 64 + 1
    assert blocks == 1281
    assert blocks * 64 * 20_480 == 1_679_032_320           # 1.68 GB


def test_decode_step_parts_at_the_cells_contexts():
    fam, m = mf.load_family(FAMILY), model()
    # Top-1 of 16: 16 tokens touch 16 (1 - (15/16)^16) = 10.30 at uniform
    # routing.
    assert fam.expected_experts_touched(m, 16) == pytest.approx(10.3028, 1e-5)
    parts = fam.decode_step_parts(m, [4500] * 16, experts_touched=10)
    assert parts == {
        "attention": 20 * (5_242_880 + 332_800) * 2,       # 0.223 GB
        "routers": 20 * 659_984 * 2,                       # 0.026 GB
        "vectors": 20 * 73_736,
        "experts_routed": 20 * 10 * 12_582_912 * 2,        # 5.03 GB
        "head": 1_074_266_112,                             # 1.07 GB
        "tail": 2 * 16 * 107_520,                          # 3.4 MB
        "kv": 16 * 4500 * 20_480,                          # 1.47 GB
    }
    total = costs.decode_step_bytes_per_chip(m, [4500] * 16, family=FAMILY)
    # Without a count: the expectation at uniform routing.
    assert total == pytest.approx(
        sum(parts.values()) + 20 * 0.3028 * 12_582_912 * 2, rel=1e-5)
    need = sum(parts.values())
    assert round(need / 1e9, 2) == 7.84
    # 9.6 ms at the chip's 819 GB/s; the K/V is a fifth of it.
    assert need / 819e9 == pytest.approx(9.57e-3, rel=1e-2)
    assert 0.18 < parts["kv"] / need < 0.20


def test_model_config_is_the_hybrid_familys_ce_pattern():
    fam, m = mf.load_family(FAMILY), model()
    cfg = fam.model_config("bench_zaya1_8b", m)
    assert cfg.family == "hybrid" and cfg.layer_period == "CE"
    assert cfg.num_layers == 40 and cfg.layers_of("C") == 20
    assert cfg.kv_layers == 20 and cfg.cca_tail_width == 2688
    assert (cfg.router_hidden, cfg.experts_per_token, cfg.num_experts,
            cfg.expert_act) == (256, 1, 16, "swiglu")
    assert cfg.rotary and cfg.qk_rope_head_dim == 64
    assert cfg.rope_theta == 5_000_000.0 and cfg.tie_embeddings
    for key, bad in (("cca_time0", 4), ("model_type", "llama"),
                     ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            fam.model_config("x", {**m, key: bad})
    with pytest.raises(ValueError, match="layer_types"):
        fam.model_config("x", {**m, "num_hidden_layers": 40})
    tiny = fam.rehearsal_model(m, m["tiers"]["nano"]["rehearsal_model"])
    assert fam.model_config("x", tiny).num_layers == 6
