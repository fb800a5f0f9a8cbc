"""families/latent_moe_decoder.py against hand-worked sizes of
Xing4.0-29B-A4B's cut (CPU, by hand: ``python3 -m pytest benchmark/tests
-q``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import costs  # noqa: E402
import manifest as mf  # noqa: E402

FAMILY = "latent_moe_decoder"


def model():
    with open(os.path.join(HERE, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_layer_sizes():
    fam, m = mf.load_family(FAMILY), model()
    # Latent attention: 3584 x 768 = 2 752 512; 768 x 32 x 192 =
    # 4 718 592; 3584 x 576 = 2 064 384; 512 x 32 x 256 = 4 194 304;
    # 32 x 128 x 3584 = 14 680 064; together 28 409 856.
    assert fam.attention_params(m) == 28_409_856
    # Stream maps: 4 x 3584 x (4 + 4 + 16) = 344 064 a sublayer, two.
    assert fam.stream_map_params(m) == 688_128
    # One expert: 3 x 3584 x 1024.
    assert fam.expert_params(m) == 11_010_048
    # Dense layer: + 3 x 3584 x 9216 = 99 090 432.
    assert fam.dense_layer_params(m) == 128_188_416
    # Expert layer without its routed experts: + shared 11 010 048 +
    # router 3584 x 64 = 229 376.
    assert fam.expert_layer_fixed_params(m) == 40_337_408


def test_weights_held_and_cache_row():
    m = model()
    # 1 dense + 5 x (40 337 408 + 64 x 11 010 048) + 2 x 131072 x 3584
    # = 128 188 416 + 3 724 902 400 + 939 524 096 = 4 792 614 912 params.
    assert costs.weight_bytes_per_chip(m, family=FAMILY) == 2 * 4_792_614_912
    # 6 layers x (512 + 64) x 2 B.
    assert costs.kv_bytes_per_token(m, family=FAMILY) == 6912


def test_decode_step_reads_the_chosen_experts_only():
    fam, m = mf.load_family(FAMILY), model()
    # Fixed: dense layer + 5 x 40 337 408 + the head 469 762 048 (not
    # the embedding) = 799 637 504 params = 1.599 GB.
    fixed = 2 * (128_188_416 + 5 * 40_337_408 + 469_762_048)
    assert fixed == 1_599_275_008
    # Experts at B = 8, uniform routing: 64 x (1 - (60/64)^8) = 25.81 a
    # layer; x 5 layers x 11 010 048 x 2 B = 2.84 GB.
    touched = 64 * (1 - (60 / 64) ** 8)
    assert fam.expected_experts_touched(m, 8) == pytest.approx(touched)
    assert touched == pytest.approx(25.81, abs=0.005)
    experts = 5 * touched * 11_010_048 * 2
    assert experts == pytest.approx(2.8417e9, rel=1e-4)
    got = costs.decode_step_bytes_per_chip(m, [3000] * 8, family=FAMILY)
    assert got == pytest.approx(fixed + experts + 8 * 3000 * 6912)
    # One sequence reads 4 experts a layer, sixty-four all of them.
    assert fam.expected_experts_touched(m, 1) == pytest.approx(4.0)
    assert fam.expected_experts_touched(m, 64) < 64
    with pytest.raises(ValueError):
        costs.decode_step_bytes_per_chip(m, [1], tp=2, family=FAMILY)
    # With the experts the program counted (23.4 a layer, say) in place of
    # the expectation: 5 x 23.4 x 11 010 048 x 2 B = 2.576 GB of experts.
    counted = fam.decode_step_bytes_per_chip(m, [3000] * 8,
                                             experts_touched=23.4)
    assert counted == pytest.approx(
        fixed + 5 * 23.4 * 11_010_048 * 2 + 8 * 3000 * 6912)
    assert counted < got


def test_published_keys_map_to_the_program():
    cfg = mf.load_family(FAMILY).model_config("x", model())
    assert cfg.latent and cfg.cache_row_width == 576
    assert (cfg.num_layers, cfg.dense_lead_layers) == (6, 1)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.shared_experts,
            cfg.moe_ffn_size) == (64, 4, 1, 1024)
    assert cfg.residual_streams == 4 and not cfg.tie_embeddings
    assert cfg.rope_factor == 64.0 and cfg.rope_original_max_pos == 4096
    bad = dict(model(), scoring_func="softmax")
    with pytest.raises(ValueError, match="scoring_func"):
        mf.load_family(FAMILY).model_config("x", bad)


def test_counted_share_takes_the_programs_count_and_the_step_by_name():
    """``step.decode_hbm_share_counted.nano`` over a recorded stretch of
    named decode ticks and counters written by hand: the experts in its
    bytes are the counted ones; without the counters it reads nothing."""
    import types
    from layer_metrics import moe_readers, named_readers
    with open(os.path.join(HERE, "tests", "data",
                           "trace_decode_closed_named.json")) as f:
        rec = json.load(f)
    m = model()
    served = types.SimpleNamespace(entries={"nano": {
        "family": FAMILY, "model": m,
        "tier": {"decode_steps_per_tick": 4, "tp": 1}}})
    counters = ('dllm_decode_ticks_total{{tier="nano"}} {}\n'
                'dllm_moe_experts_touched_total{{tier="nano",stage="decode"}}'
                ' {}\n')
    ctx = types.SimpleNamespace(
        served=served, trace=rec, host_span=(10.0, 11.0), records=[],
        peaks={"hbm_bytes_per_s": 819e9},
        tier_traces=lambda tier: [rec["devices"]["0"]],
        metrics_before=counters.format(10, 1000),
        # 10 more ticks of 4 steps over 5 expert layers: 20 a step a layer.
        metrics_after=counters.format(20, 1000 + 10 * 4 * 5 * 20))
    assert moe_readers.experts_touched_per_step(ctx, "nano") == 20.0
    step = named_readers.decode_step_ms(ctx, "nano")
    need = 1_599_275_008 + 5 * 20.0 * 11_010_048 * 2
    assert moe_readers.decode_hbm_share_counted(ctx, "nano") == \
        pytest.approx(100.0 * need / 819e9 / (step / 1000.0))
    ctx.metrics_before = ctx.metrics_after = ""
    assert moe_readers.decode_hbm_share_counted(ctx, "nano") is None
