"""The rotary latent attention / routed-expert family's counts
(``families/rotary_latent_moe_decoder.py``, reached through ``costs.py`` by
the tier's family) held to sizes worked by hand for
``configs/sarvam-105b.json``.  By hand, ``JAX_PLATFORMS=cpu python3 -m
pytest benchmark/tests/test_costs_rotary_latent_moe.py -q``."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import costs                                           # noqa: E402
import manifest as mf                                  # noqa: E402

FAMILY = "rotary_latent_moe_decoder"
SARVAM = mf.load_json("configs", "sarvam-105b.json")
fam = mf.load_family(FAMILY)

H = 4096
# One "L" mixer: W_q 4096 x 64 x 192, W_kva 4096 x 576, the latent norm's
# gain 512, W_kvb 512 x 64 x 256, W_o 8192 x 4096.
LATENT = 50_331_648 + 2_359_296 + 512 + 8_388_608 + 33_554_432
assert LATENT == 94_634_496
EXPERT = 3 * H * 2048                               # 25 165 824
LEAD_MLP = 3 * H * 16_384                           # 201 326 592
ROUTER = (H + 1) * 128                              # 524 416
EMBED = 65_536 * H                                  # 268 435 456
NORMS = 13 * H                                      # two a layer + final
HELD = (6 * LATENT + LEAD_MLP + 5 * (ROUTER + EXPERT + 32 * EXPERT)
        + NORMS + 2 * EMBED)


def test_parameters_a_sublayer_of_each_kind_by_hand():
    assert fam.pattern("sarvam", SARVAM) == "L-" + "LE" * 5
    assert fam.latent_mixer_params(SARVAM) == LATENT
    assert fam.expert_params(SARVAM) == EXPERT == 25_165_824
    assert fam.shared_expert_params(SARVAM) == EXPERT
    assert fam.lead_mlp_params(SARVAM) == LEAD_MLP == 201_326_592
    assert fam.router_params(SARVAM) == ROUTER
    assert fam.embed_params(SARVAM) == EMBED
    assert fam.norm_params(SARVAM) == NORMS
    assert fam.held_params(SARVAM) == HELD == 5_461_040_768


def test_weights_held_are_the_motivations_10_92_gb():
    # ISSUE 59: the lead layer 0.592 GB, an expert layer 1.851 GB (925.6 M
    # parameters), a quarter of the vocabulary twice 1.074 GB.
    assert (LATENT + LEAD_MLP) * 2 == pytest.approx(0.592e9, rel=1e-3)
    layer = LATENT + ROUTER + 33 * EXPERT
    assert layer == pytest.approx(925.6e6, rel=1e-4)
    assert layer * 2 == pytest.approx(1.851e9, rel=1e-3)
    assert 2 * EMBED * 2 == pytest.approx(1.074e9, rel=1e-3)
    held = costs.weight_bytes_per_chip(SARVAM, family=FAMILY)
    assert held == 2 * HELD == 10_922_081_536
    assert held == pytest.approx(10.92e9, rel=1e-3)
    with pytest.raises(ValueError, match="one chip a share"):
        fam.weight_bytes_per_chip(SARVAM, 2)


def test_a_token_keeps_six_latent_rows():
    # 6 layers x (512 + 64) numbers x 2 bytes; the pool rests a row at 640.
    assert costs.kv_bytes_per_token(SARVAM, family=FAMILY) == 6912
    pool_blocks, block = 4 * 260 + 1, 64
    assert 6 * pool_blocks * block * 640 * 2 == pytest.approx(0.51e9,
                                                              rel=5e-3)
    # Weights and pool fill two thirds of the chip before any temporary.
    filled = 2 * HELD + 6 * pool_blocks * block * 640 * 2
    assert 0.66 < filled / (16 * 2 ** 30) < 0.67


def test_a_decode_step_by_part():
    contexts = [16_400, 16_300]
    parts = fam.decode_step_parts(SARVAM, contexts, experts_touched=4.0)
    assert parts == {
        "latent_mixers": 6 * LATENT * 2,
        "routers": 5 * ROUTER * 2,
        "lead_mlp": LEAD_MLP * 2,
        "experts_shared": 5 * EXPERT * 2,
        "experts_routed": 5 * 4.0 * EXPERT * 2,
        "norms": NORMS * 2,
        "head": EMBED * 2,
        "kv": 32_700 * 6912,
    }
    assert sum(parts.values()) == costs.decode_step_bytes_per_chip(
        SARVAM, contexts, family=FAMILY) - (
            fam.expected_experts_touched(SARVAM, 2) - 4.0) * 5 * EXPERT * 2
    # Two tokens at uniform routing touch 32 (1 - (120/128)^2) = 3.875 of
    # the 32 held experts a layer.
    assert fam.expected_experts_touched(SARVAM, 2) == pytest.approx(3.875)
    # About 3.56 GB a step: 4.4 ms at 819 GB/s.
    assert sum(parts.values()) == pytest.approx(3.565e9, rel=0.001)


def test_a_chunk_programs_matrix_products_by_hand():
    steps, window = 256, 16_384
    # A latent layer: the chunk's three projections (W_q, W_kva, W_o), the
    # up-projection of the WHOLE rung's latent rows (275 GFLOP), scores
    # over 192 and values over 128 numbers a head over the whole rung (172
    # GFLOP: ISSUE 59's count).
    projections = 2 * steps * (50_331_648 + 2_359_296 + 33_554_432)
    up = 2 * window * 512 * 64 * 256
    scores = 2 * steps * window * 64 * (192 + 128)
    assert up == 274_877_906_944 and scores == 171_798_691_840
    # An expert layer: the float32 router, the shared expert, and the 256
    # x 8 x 32 / 128 = 512 assignments uniform routing sends the held
    # experts (16 tokens an expert: the deployment's count for a chunk).
    experts = 2 * steps * (H * 128 + EXPERT) + 2 * 512 * EXPERT
    want = (6 * (projections + up + scores) + 2 * steps * LEAD_MLP
            + 5 * experts + 2 * EMBED)
    assert fam.chunk_flops_per_chip(SARVAM, steps, window) == want
    assert want == pytest.approx(3.25e12, rel=0.01)
    # At the first rung the window's part is a 64th.
    small = fam.chunk_flops_per_chip(SARVAM, steps, 256)
    assert want - small == 6 * (up + scores) * 63 // 64
    assert fam.chunk_loops(SARVAM) == 1


def test_the_mapping_states_every_published_width():
    cfg = fam.model_config("bench_sarvam_105b", SARVAM)
    assert cfg.layer_pattern == "L-" + "LE" * 5 and cfg.rotary
    assert (cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (64, 512, 128, 64, 128)
    assert (cfg.rope_factor, cfg.rope_original_max_pos, cfg.rope_beta_fast,
            cfg.rope_beta_slow, cfg.rope_mscale, cfg.rope_mscale_all_dim) \
        == (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert (cfg.hidden_size, cfg.ffn_size, cfg.moe_ffn_size,
            cfg.shared_ffn_size) == (4096, 16_384, 2048, 2048)
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_count,
            cfg.experts_per_token, cfg.router_scale) == (128, 0, 32, 8, 2.5)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) \
        == (65_536, 16_640, 1e-6)
    assert cfg.cache_row_width == 576 and cfg.cache_row_rest_width == 640
    # The span is whole chunks and whole blocks.
    assert 16_640 % 256 == 0 and 16_640 // 64 == 260
