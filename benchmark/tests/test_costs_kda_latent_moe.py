"""families/kda_latent_moe_decoder.py against hand-worked sizes of
Kimi-Linear-48B-A3B's stage 1, rank 0 (CPU, by hand: ``python3 -m pytest
benchmark/tests -q``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import costs  # noqa: E402
import manifest as mf  # noqa: E402

FAMILY = "kda_latent_moe_decoder"


def model():
    with open(os.path.join(HERE, "configs", "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def test_parameters_a_sublayer():
    fam, m = mf.load_family(FAMILY), model()
    # W_q, W_k, W_v 2304 x 4096 and W_o 4096 x 2304 = 4 x 9 437 184; the
    # decay's and the gate's low-rank pairs 2 x (2304 x 128 + 128 x 4096)
    # = 1 638 400; beta 2304 x 32 = 73 728; three convs of 4 taps x 4096 =
    # 49 152; A_log 32, dt_bias 4096, the output norm's gain 128: 39.51 M.
    assert fam.kda_mixer_params(m) == (
        37_748_736 + 1_638_400 + 73_728 + 49_152 + 32 + 4096 + 128
    ) == 39_514_272
    # W_q 2304 x 32 x 192 = 14 155 776; W_kva 2304 x 576 = 1 327 104; the
    # latent's gain 512; W_kvb 512 x 32 x 256 = 4 194 304; W_o 4096 x 2304
    # = 9 437 184: 29.11 M.
    assert fam.latent_mixer_params(m) == 29_114_880
    # One gated expert 3 x 2304 x 1024 = 7.08 M; the shared one alike; the
    # router 2304 x 256 and its bias.
    assert fam.expert_params(m) == fam.shared_expert_params(m) == 7_077_888
    assert fam.router_params(m) == 2304 * 256 + 256 == 590_080
    assert fam.lead_mlp_params(m) == 3 * 2304 * 9216 == 63_700_992


def test_weights_held_by_the_share_and_the_whole_model():
    fam, m = mf.load_family(FAMILY), model()
    assert fam._counts(m) == (7, 2, 1, 8)
    outside = 39_514_272 + 590_080 + 7_077_888             # 47.18 M
    kda_layer = outside + 64 * 7_077_888
    assert kda_layer == 500_167_072                        # 500.2 M
    latent_layer = 29_114_880 + 590_080 + 7_077_888 + 64 * 7_077_888
    assert latent_layer == 489_767_680                     # 489.8 M
    lead = 39_514_272 + 63_700_992                         # 103.2 M
    tables = 2 * 40_960 * 2304                             # 188.7 M
    norms = 19 * 2304
    held = 6 * kda_layer + 2 * latent_layer + lead + tables + norms
    assert fam.held_params(m) == held == 4_272_540_512
    total = costs.weight_bytes_per_chip(m, family=FAMILY)
    assert total == 2 * held
    # The issue's table: 8.55 GB, within 1 %.
    assert total / 1e9 == pytest.approx(8.55, rel=0.01)
    assert total / 17.18e9 > 0.49
    with pytest.raises(ValueError, match="one chip a share"):
        fam.weight_bytes_per_chip(m, 2)
    # The uncut model: 27 layers, 256 experts, 163 840 rows: 49.1 B.
    whole = {**m, **m["published"], "router_outputs": 256}
    assert fam._counts(whole) == (20, 7, 1, 26)
    assert fam.held_params(whole) / 1e9 == pytest.approx(49.1, rel=0.02)
    # One whole expert layer is 3.72 GB: a chip cannot hold a period of 4.
    assert (39_514_272 + 590_080 + 257 * 7_077_888) * 2 / 1e9 \
        == pytest.approx(3.72, rel=0.01)


def test_rows_and_latent_cache():
    fam, m = mf.load_family(FAMILY), model()
    # The normalised latent (512) and the shared k_pe (64) in bfloat16 over
    # the 2 latent layers: 1152 B a layer.
    assert costs.kv_bytes_per_token(m, family=FAMILY) == 2 * 1152 == 2304
    # A KDA layer: 32 heads x 128 x 128 float32 and 3 rows of 3 x 4096
    # conv inputs in bfloat16.
    assert fam.state_bytes_per_slot(m) == 7 * (2_097_152 + 73_728) \
        == 15_196_160
    # 16 slots x 5120 positions in blocks of 64, + the trash block.
    blocks = 16 * 5120 // 64 + 1
    assert blocks == 1281
    assert blocks * 64 * 2304 == 188_891_136               # 0.19 GB
    assert 16 * 15_196_160 == 243_138_560                  # 0.24 GB


def test_decode_step_parts_at_the_cells_contexts():
    fam, m = mf.load_family(FAMILY), model()
    # Top-8 of 256 of which 64 are held: 16 tokens touch 64 (1 -
    # (248/256)^16) = 25.49 of them at uniform routing.
    assert fam.expected_experts_touched(m, 16) == pytest.approx(25.4905,
                                                                1e-5)
    parts = fam.decode_step_parts(m, [4500] * 16, experts_touched=25)
    assert parts == {
        "kda_mixers": 7 * 39_514_272 * 2,                  # 0.553 GB
        "latent_mixers": 2 * 29_114_880 * 2,               # 0.116 GB
        "routers": 8 * 590_080 * 2,
        "lead_mlp": 63_700_992 * 2,                        # 0.127 GB
        "experts_shared": 8 * 7_077_888 * 2,               # 0.113 GB
        "experts_routed": 8 * 25 * 7_077_888 * 2,          # 2.83 GB
        "norms": 19 * 2304 * 2,
        "head": 40_960 * 2304 * 2,                         # 0.189 GB
        "state": 2 * 16 * 15_196_160,                      # 0.486 GB
        "kv": 16 * 4500 * 2304,                            # 0.166 GB
    }
    total = costs.decode_step_bytes_per_chip(m, [4500] * 16, family=FAMILY)
    # Without a count: the expectation at uniform routing.
    assert total == pytest.approx(
        sum(parts.values()) + 8 * 0.4905 * 7_077_888 * 2, rel=1e-5)
    need = sum(parts.values())
    assert round(need / 1e9, 2) == 4.59
    # 5.6 ms at the chip's 819 GB/s; the experts are three fifths of it,
    # the recurrent rows a tenth.
    assert need / 819e9 == pytest.approx(5.6e-3, rel=1e-2)
    assert 0.60 < parts["experts_routed"] / need < 0.63
    assert 0.10 < parts["state"] / need < 0.11


def test_model_config_is_the_hybrid_familys_lead_and_period():
    fam, m = mf.load_family(FAMILY), model()
    assert fam.pattern("x", m) == "K-" + "KEKELEKE" * 2
    cfg = fam.model_config("bench_kimi_linear_48b_a3b", m)
    assert cfg.family == "hybrid" and not cfg.latent
    assert cfg.layer_segments == (("K", 1), ("-", 1), ("KEKELEKE", 2))
    assert cfg.layer_lead == "K-" and cfg.layer_period == "KEKELEKE"
    assert cfg.num_layers == 18 and cfg.kv_layers == 2
    assert [cfg.layers_of(k) for k in "KLE-"] == [7, 2, 8, 1]
    assert cfg.cache_row_width == 576 and cfg.ssm_conv_width == 12_288
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv) == (32, 128, 4)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_act, cfg.router_scale) == (256, 64, 8, "swiglu",
                                                  2.446)
    assert (cfg.ffn_size, cfg.moe_ffn_size, cfg.shared_ffn_size) \
        == (9216, 1024, 1024)
    assert not cfg.rotary and not cfg.tie_embeddings and not cfg.q_lora_rank
    assert cfg.max_seq_len == 5120 and cfg.vocab_size == 40_960
    # The uncut model is 27 layers: a lead and six periods and a tail that
    # breaks the period (layers 26-27: K, L), so nothing leads ONE loop.
    whole = fam.model_config("x", {**m, **m["published"],
                                   "num_experts": 256})
    assert whole.num_layers == 54 and whole.layers_of("L") == 7
    for key, bad in (("mla_use_nope", False), ("model_type", "deepseek_v3"),
                     ("q_lora_rank", 1536), ("moe_renormalize", False),
                     ("num_expert_group", 8)):
        with pytest.raises(ValueError, match=key):
            fam.model_config("x", {**m, key: bad})
    with pytest.raises(ValueError, match="ONE of"):
        fam.model_config("x", {**m, "num_hidden_layers": 10})
    with pytest.raises(ValueError, match="not among the router's"):
        fam.model_config("x", {**m, "first_routed_expert": 200})
    tiny = fam.rehearsal_model(m, m["tiers"]["nano"]["rehearsal_model"])
    assert fam.model_config("x", tiny).layer_pattern == "K-KEKELEKE"


def test_the_file_keeps_every_published_width():
    m = model()
    assert m["reduced"] == ["num_hidden_layers", "linear_attn_config",
                            "num_experts", "vocab_size", "model_max_length"]
    assert set(m["published"]) == set(m["reduced"]) == set(m["reduced_why"])
    lin, pub = m["linear_attn_config"], m["published"]["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lin[key] == pub[key]
    assert lin["kda_layers"] == [n for n in pub["kda_layers"] if n <= 9]
    assert lin["full_attn_layers"] == [n for n in pub["full_attn_layers"]
                                       if n <= 9]
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["num_experts_per_token"], m["router_outputs"],
            m["routed_scaling_factor"], m["num_shared_experts"]) \
        == (2304, 9216, 1024, 512, 128, 64, 128, 8, 256, 2.446, 1)
