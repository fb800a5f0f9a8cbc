"""families/ssm_window_shared_kv_decoder.py against hand-worked sizes of
Phi-4-mini-flash-reasoning (CPU, by hand: ``python3 -m pytest
benchmark/tests -q``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import costs  # noqa: E402
import manifest as mf  # noqa: E402

FAMILY = "ssm_window_shared_kv_decoder"


def model():
    with open(os.path.join(HERE, "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def test_layer_sizes():
    fam, m = mf.load_family(FAMILY), model()
    # W1 2560 x 20480 = 52 428 800, W2 10240 x 2560 = 26 214 400.
    assert fam.mlp_params(m) == 78_643_200
    # M: in 2560 x 10240 = 26 214 400; conv 4 x 5120 + 5120 = 25 600;
    # to [dt | B | C] 5120 x 192 = 983 040; dt up 160 x 5120 + 5120 =
    # 824 320; A_log 5120 x 16 = 81 920; D 5120; out 5120 x 2560 =
    # 13 107 200.
    assert fam.ssm_mixer_params(m) == 41_241_600
    # G: 2560 x 5120 in, 5120 x 2560 out.
    assert fam.memory_unit_params(m) == 26_214_400
    # W and F: q|k|v 2560 x 5120 + 5120; o 2560 x 2560 + 2560; 4 lambda
    # vectors of 64; the pair norm's gain 128.
    assert fam.attention_mixer_params(m) == 19_668_864
    # X: q 2560 x 2560 + 2560; o the same; lambdas and gain 384.
    assert fam.cross_mixer_params(m) == 13_112_704
    # 32 x 2 LayerNorms and the final one, gain and bias: 65 x 5120.
    assert fam.norm_params(m) == 332_800
    assert fam.embed_params(m) == 512_163_840


def test_the_whole_model_is_the_catalogs_three_point_eight_billion():
    fam, m = mf.load_family(FAMILY), model()
    total = (32 * 78_643_200 + 9 * 41_241_600 + 7 * 26_214_400
             + 9 * 19_668_864 + 7 * 13_112_704 + 332_800 + 512_163_840)
    assert total == 3_852_562_944
    assert fam.param_count(m) == total
    assert costs.weight_bytes_per_chip(m, family=FAMILY) == 7_705_125_888
    # 45 % of the chip's 16 GiB before pool, rings and state.
    assert 0.44 < 7_705_125_888 / 17_179_869_184 < 0.46
    with pytest.raises(ValueError, match="one chip"):
        costs.weight_bytes_per_chip(m, 2, family=FAMILY)


def test_cache_rings_and_state():
    fam, m = mf.load_family(FAMILY), model()
    # K and V of 20 heads x 64 in the ONE cached layer, 2 B each.
    assert costs.kv_bytes_per_token(m, family=FAMILY) == 5120
    assert fam.kv_readers(m) == 8                      # F and 7 X
    # A layer: S 5120 x 16 x 4 B = 327 680; tail 3 x 5120 x 2 B = 30 720.
    assert fam.state_bytes_per_slot(m) == 9 * 358_400 == 3_225_600
    # A layer: K and V of 512 positions x 1280 x 2 B = 2 621 440.
    assert fam.ring_bytes_per_slot(m) == 8 * 2_621_440 == 20_971_520
    # Neither grows with the sequence: 24.2 MB a slot, 0.39 GB for 16.
    assert 16 * (3_225_600 + 20_971_520) == 387_153_920


def test_decode_step_bytes_by_part():
    fam, m = mf.load_family(FAMILY), model()
    contexts = [4500] * 16
    parts = fam.decode_step_parts(m, contexts)
    assert parts["mlps"] == 32 * 78_643_200 * 2 == 5_033_164_800
    mixers = (9 * 41_241_600 + 7 * 26_214_400 + 9 * 19_668_864
              + 7 * 13_112_704 + 332_800)
    assert parts["mixers"] == mixers * 2 == 1_647_633_408
    assert parts["head"] == 1_024_327_680
    # 8 readers x 16 slots x 4500 positions x 5120 B.
    assert parts["shared_kv"] == 8 * 16 * 4500 * 5120 == 2_949_120_000
    assert parts["rings"] == 16 * 20_971_520 == 335_544_320
    assert parts["state"] == 2 * 16 * 3_225_600 == 103_219_200
    total = costs.decode_step_bytes_per_chip(m, contexts, family=FAMILY)
    assert total == sum(parts.values()) == 11_093_009_408
    # 13.5 ms at 819 GB/s; the one cached layer's readers are 27 % of it.
    assert 13.4e-3 < total / 819e9 < 13.6e-3
    assert 0.26 < parts["shared_kv"] / total < 0.27


def test_chunk_scan_kernel_counts():
    fam, m = mf.load_family(FAMILY), model()
    # 256 positions x 5120 channels x (7 x 16 states + 1).
    assert fam.ssm_chunk_scan_ops(m, 256) == 256 * 5120 * 113 \
        == 148_111_360
    # dt, u in and y out 3 x 256 x 5120; B and C spread over 128 lanes
    # 2 x 256 x 16 x 128; A, S in and S out 3 x 16 x 5120; float32.
    assert fam.ssm_chunk_scan_bytes(m, 256) == 4 * (
        3_932_160 + 1_048_576 + 245_760) == 20_905_984
    # Bound by its bytes at the chip's peaks: 25.5 us against 0.75.
    assert 20_905_984 / 819e9 > 30 * 148_111_360 / 197e12


def test_program_config_is_the_published_block():
    fam, m = mf.load_family(FAMILY), model()
    cfg = fam.model_config("p", m)
    assert cfg.shared_kv and cfg.hybrid and not cfg.latent
    assert cfg.layer_segments == (("MW", 8), ("M", 1), ("F", 1), ("GX", 7))
    assert (cfg.head_dim, cfg.cache_row_width, cfg.ssm_inner,
            cfg.ssm_conv_width) == (64, 1280, 5120, 5120)
    assert (cfg.attn_window, cfg.ssm_dt_rank, cfg.ssm_state) == (512, 160, 16)
    with pytest.raises(ValueError, match="phi4flash"):
        fam.model_config("p", dict(m, model_type="phi3"))
    # Nothing is cut but the positions served.
    assert m["reduced"] == ["max_position_embeddings"]
    assert m["published"] == {"max_position_embeddings": 262144}


def test_chunk_programs_are_parted_by_the_loops_each_ran():
    """``shared_kv_readers`` reads the device: a chunk program that holds
    ONE ``while`` stopped at the cached layer, one that holds TWO ran
    every layer.  A program without the skip (every execution two loops)
    reads no self-only time at all, whatever the scheduler counted."""
    import types
    from layer_metrics import shared_kv_readers as readers
    fam, m = mf.load_family(FAMILY), model()
    # "MW" x 8 before the cached layer; "GX" x 7 behind it.
    assert fam.chunk_loops(m) == (1, 2)

    ms = 1_000_000

    def ctx_of(programs):
        """``programs``: (start ms, length ms, loops inside) each."""
        modules, ops = [], [["fusion.1", 0, 10]]
        for start, dur, loops in programs:
            modules.append(["jit_chunk_prefill(123)", start * ms, dur * ms])
            for i in range(loops):
                ops.append([f"while.{i}", start * ms + (1 + 4 * i) * ms,
                            3 * ms])
        # A decode tick between them: its loops are not a chunk's.
        modules.append(["jit_decode_tick(9)", 500 * ms, 70 * ms])
        ops.append(["while.7", 501 * ms, 60 * ms])
        dev = {"modules": modules, "ops": ops}
        return types.SimpleNamespace(
            served=types.SimpleNamespace(entries={"nano": {
                "family": FAMILY, "model": m, "tier": {}}}),
            trace={"t_lo": 0, "t_hi": 1000 * ms},
            tier_traces=lambda tier: [dev])

    ctx = ctx_of([(100, 10, 1), (200, 12, 1), (300, 20, 2), (400, 14, 1)])
    assert readers.chunk_ms_self_only(ctx, "nano") == pytest.approx(12.0)
    assert readers.chunk_ms_full_depth(ctx, "nano") == pytest.approx(20.0)
    # The skip taken out of the program: nothing is self-only.
    ctx = ctx_of([(100, 20, 2), (200, 20, 2), (300, 20, 2)])
    assert readers.chunk_ms_self_only(ctx, "nano") is None
    assert readers.chunk_ms_full_depth(ctx, "nano") == pytest.approx(20.0)
    # Another program than the family describes; another family; no chunk.
    assert readers.chunk_ms_self_only(ctx_of([(100, 20, 3)]), "nano") is None
    other = ctx_of([(100, 10, 1)])
    other.served.entries["nano"]["family"] = "hybrid_ssm_moe_decoder"
    assert readers.chunk_ms_self_only(other, "nano") is None
    assert readers.chunk_ms_full_depth(ctx_of([]), "nano") is None
