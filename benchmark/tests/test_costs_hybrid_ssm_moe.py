"""families/hybrid_ssm_moe_decoder.py against hand-worked sizes of
NVIDIA-Nemotron-3-Nano-30B-A3B's cut (CPU, by hand: ``python3 -m pytest
benchmark/tests -q``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import costs  # noqa: E402
import manifest as mf  # noqa: E402

FAMILY = "hybrid_ssm_moe_decoder"


def model():
    with open(os.path.join(HERE, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def test_layer_sizes():
    fam, m = mf.load_family(FAMILY), model()
    # M: in 2688 x (4096 + 6144 + 64) = 27 697 152; conv 6144 x 4 + 6144
    # = 30 720; dt_bias, A_log, D 3 x 64; gated norm 4096; out 4096 x
    # 2688 = 11 010 048; pre-norm 2688.
    assert fam.ssm_layer_params(m) == 38_744_896
    # *: q and o 2 x 2688 x 4096 = 22 020 096; k and v 2 x 2688 x 256 =
    # 1 376 256; pre-norm 2688.
    assert fam.attention_layer_params(m) == 23_399_040
    # One non-gated expert: 2 x 2688 x 1856.
    assert fam.expert_params(m) == 9_977_856
    # E without its routed experts: shared 2 x 2688 x 3712 = 19 955 712,
    # router 2688 x 128 = 344 064, its bias 128, pre-norm 2688.
    assert fam.shared_expert_params(m) == 19_955_712
    assert fam.expert_layer_fixed_params(m) == 20_302_592


def test_weights_held_by_the_share():
    m = model()
    experts = 6 * (64 * 9_977_856 + 20_302_592) * 2
    assert experts == 7_906_624_512                       # 7.907 GB
    mixers = 6 * 38_744_896 * 2 + 2 * 23_399_040 * 2
    assert mixers == 464_938_752 + 93_596_160             # 0.465 + 0.094
    tables = 2 * 131_072 * 2688 * 2
    assert tables == 1_409_286_144                        # 1.409 GB
    total = costs.weight_bytes_per_chip(m, family=FAMILY)
    assert total == experts + mixers + tables == 9_874_445_568   # 9.88 GB
    # Over a quarter of a 16 GB chip; the whole layer's 128 experts would
    # be 2 595 GB x 6 = 15.6 GB of experts alone.
    assert total / 16e9 > 0.6


def test_cache_and_state():
    fam, m = mf.load_family(FAMILY), model()
    # K and V of 2 heads x 128 over the 2 ATTENTION layers, 2 B each.
    assert costs.kv_bytes_per_token(m, family=FAMILY) == 2 * 1024
    # A layer: S 64 x 64 x 128 x 4 B = 2 097 152; tail 3 x 6144 x 2 B =
    # 36 864.
    assert fam.state_bytes_per_slot(m, layers=1) == 2_134_016
    assert fam.state_bytes_per_slot(m) == 6 * 2_134_016 == 12_804_096


def test_decode_step_moves_the_chosen_experts_and_the_state_twice():
    fam, m = mf.load_family(FAMILY), model()
    # 16 tokens, uniform routing over 128 outputs, 6 a token: a held
    # expert is missed by a token with probability 122/128.
    touched = 64 * (1 - (122 / 128) ** 16)
    assert fam.expected_experts_touched(m, 16) == pytest.approx(touched)
    assert touched == pytest.approx(34.3, abs=0.05)
    contexts = [900.0] * 16
    parts = fam.decode_step_parts(m, contexts)
    assert parts["mixers"] == 464_938_752 + 93_596_160          # 0.56 GB
    assert parts["experts_fixed"] == 6 * 20_302_592 * 2         # 0.24 GB
    assert parts["experts_routed"] == pytest.approx(
        6 * touched * 9_977_856 * 2)                            # 4.1 GB
    assert parts["head"] == 704_643_072                         # 0.70 GB
    assert parts["state"] == 2 * 16 * 12_804_096                # 0.41 GB
    assert parts["kv"] == 16 * 900 * 2048
    total = costs.decode_step_bytes_per_chip(m, contexts, family=FAMILY)
    assert total == pytest.approx(sum(parts.values()))
    assert total / 1e9 == pytest.approx(6.06, abs=0.02)   # 7.4 ms at 819 GB/s
    counted = fam.decode_step_bytes_per_chip(m, contexts,
                                             experts_touched=30.0)
    assert counted == pytest.approx(
        total - 6 * (touched - 30.0) * 9_977_856 * 2)
    with pytest.raises(ValueError, match="one chip"):
        fam.decode_step_bytes_per_chip(m, contexts, tp=2)


def test_model_config_refuses_other_architectures_by_name():
    fam, m = mf.load_family(FAMILY), model()
    cfg = fam.model_config("p", m)
    assert cfg.layer_pattern == "MEMEM*EMEMEM*E" and cfg.num_layers == 14
    assert cfg.layer_period == "MEMEM*E"
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held) == \
        (128, 0, 64)
    assert cfg.head_dim == 128 and cfg.cache_row_width == 256
    assert cfg.ssm_inner == 4096 and cfg.ssm_conv_width == 6144
    assert not cfg.rotary and not cfg.tie_embeddings
    for key, bad in (("mlp_hidden_act", "silu"), ("n_group", 2),
                     ("use_conv_bias", False), ("model_type", "llama")):
        with pytest.raises(ValueError, match=key):
            fam.model_config("p", dict(m, **{key: bad}))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        fam.model_config("p", dict(m, num_hidden_layers=13))
    with pytest.raises(ValueError, match="not among the router's"):
        fam.model_config("p", dict(m, first_routed_expert=65))
    small = fam.rehearsal_model(m, m["tiers"]["nano"]["rehearsal_model"])
    assert fam.model_config("p", small).layer_period == "MEM*E"
