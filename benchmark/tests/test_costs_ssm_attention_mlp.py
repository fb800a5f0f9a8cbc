"""families/ssm_attention_mlp_decoder.py against hand-worked sizes of
AI21-Jamba2-3B, and its readers on a made-up trace (CPU, by hand:
``python3 -m pytest benchmark/tests -q``)."""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import costs  # noqa: E402
import manifest as mf  # noqa: E402

FAMILY = "ssm_attention_mlp_decoder"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def model():
    with open(os.path.join(HERE, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def test_layer_sizes():
    fam, m = mf.load_family(FAMILY), model()
    # Gate, up and down: 3 x 2560 x 8192.
    assert fam.mlp_params(m) == 62_914_560
    # M: in 2560 x 10240 = 26 214 400; conv 4 x 5120 + 5120 = 25 600;
    # to [dt | B | C] 5120 x 192 = 983 040; the three inner norms' gains
    # 160 + 16 + 16 = 192; dt up 160 x 5120 + 5120 = 824 320; A_log 5120
    # x 16 = 81 920; D 5120; out 5120 x 2560 = 13 107 200.
    assert fam.ssm_matrix_params(m) == 41_123_840
    assert fam.ssm_mixer_params(m) == 41_241_792
    # q and o 2560 x 2560 each, k and v 2560 x 128 each (ONE K/V head).
    assert fam.attention_mixer_params(m) == 13_762_560
    # Two pre-norm gains a layer and the final one: 57 x 2560.
    assert fam.norm_params(m) == 145_920
    assert fam.embed_params(m) == 167_772_160
    assert fam.mixers(m) == "MMMMMMM*MMMMMM" * 2


def test_the_whole_model_is_the_catalogs_three_billion():
    fam, m = mf.load_family(FAMILY), model()
    total = (26 * (41_241_792 + 62_914_560) + 2 * (13_762_560 + 62_914_560)
             + 145_920 + 167_772_160)
    assert total == 3_029_337_472
    assert fam.param_count(m) == total
    assert costs.weight_bytes_per_chip(m, family=FAMILY) == 6_058_674_944
    # 35 % of the chip's 16 GiB before pool and state.
    assert 0.35 < 6_058_674_944 / 17_179_869_184 < 0.36
    with pytest.raises(ValueError, match="one chip"):
        costs.weight_bytes_per_chip(m, 2, family=FAMILY)


def test_cache_and_state():
    fam, m = mf.load_family(FAMILY), model()
    # 2 attention layers x K and V x ONE head of 128 x 2 B.
    assert costs.kv_bytes_per_token(m, family=FAMILY) == 1024
    # 26 x (5120 x 16 float32 + 3 x 5120 bf16) = 26 x 358 400.
    assert fam.state_bytes_per_slot(m) == 9_318_400
    # The pool of the cell: 4 slots x 512 blocks + the trash block.
    assert 2049 * 64 * 1024 == 134_283_264


def test_decode_step_bytes_by_part():
    fam, m = mf.load_family(FAMILY), model()
    parts = fam.decode_step_parts(m, [16400.0, 16300.0])
    assert parts["mlps"] == 28 * 62_914_560 * 2
    assert parts["mixers"] == (26 * 41_241_792 + 2 * 13_762_560
                               + 145_920) * 2
    assert parts["head"] == 167_772_160 * 2
    assert parts["kv"] == 32700 * 1024
    assert parts["state"] == 2 * 2 * 9_318_400
    assert sum(parts[k] for k in ("mlps", "mixers", "head")) == 6_058_674_944
    # The weights are 99 % of such a step.
    whole = costs.decode_step_bytes_per_chip(m, [16400.0, 16300.0],
                                             family=FAMILY)
    assert whole == sum(parts.values())
    assert 0.98 < 6_058_674_944 / whole < 0.995


def test_chunk_scan_kernel_counts():
    fam, m = mf.load_family(FAMILY), model()
    # 256 positions x 5120 channels x (7 x 16 states + 1).
    assert fam.ssm_chunk_scan_ops(m, 256) == 256 * 5120 * 113 == 148_111_360
    # float32: dt, u read and y written 3 x 256 x 5120; B and C spread
    # over a lane width 2 x 256 x 16 x 128; A, the state in and out 3 x 16
    # x 5120.
    assert fam.ssm_chunk_scan_bytes(m, 256) == 4 * (3_932_160 + 1_048_576
                                                    + 245_760)


def test_chunk_flops_are_the_matrix_products_of_one_chunk_program():
    fam, m = mf.load_family(FAMILY), model()
    steps, window = 256, 16384
    per_position = (
        26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)   # M
        + 2 * (2 * 2560 * 2560 + 2 * 2560 * 128)                      # *
        + 28 * 3 * 2560 * 8192)                                       # MLPs
    # 1 069 219 840 + 27 525 120 + 1 761 607 680.
    assert per_position == 2_858_352_640
    scores = 2 * (2 * 2 * steps * window * 20 * 128)   # QK^T and PV, 2 layers
    head = 2 * 65536 * 2560                            # one position's logits
    want = 2 * steps * per_position + scores + head
    assert want == 1_463_476_551_680 + 85_899_345_920 + 335_544_320
    assert fam.chunk_flops_per_chip(m, steps, window) == want
    # 7.4 ms of the chip's 197 TFLOP/s at the narrowest rung.
    assert 7.4e-3 < fam.chunk_flops_per_chip(m, 256, 256) / 197e12 < 7.5e-3
    assert fam.chunk_loops(m) == 1


def test_program_config_is_the_published_block():
    fam, m = mf.load_family(FAMILY), model()
    cfg = fam.model_config("p", m)
    assert cfg.family == "hybrid" and cfg.hybrid and not cfg.shared_kv
    assert cfg.layer_pattern == "M-M-M-M-M-M-M-*-M-M-M-M-M-M-" * 2
    assert cfg.layer_segments == (("M-M-M-M-M-M-M-*-M-M-M-M-M-M-", 2),)
    assert (cfg.head_dim, cfg.cache_row_width, cfg.kv_layers, cfg.ssm_inner,
            cfg.ssm_conv_width) == (128, 128, 2, 5120, 5120)
    assert (cfg.ssm_dt_rank, cfg.ssm_state, cfg.ffn_size,
            cfg.tie_embeddings, cfg.norm_eps) == (160, 16, 8192, True, 1e-6)
    with pytest.raises(ValueError, match="jamba"):
        fam.model_config("p", dict(m, model_type="bamba"))
    with pytest.raises(ValueError, match="num_experts"):
        fam.model_config("p", dict(m, num_experts=16))
    # Nothing is cut but the positions served.
    assert m["reduced"] == ["max_position_embeddings"]
    assert m["published"] == {"max_position_embeddings": 262144}


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalogs_config():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    m = model()
    differs = sorted(k for k, v in row["config"].items() if m.get(k) != v)
    assert differs == m["reduced"]
    assert m["source"] == row["source_url"]


MS = 1_000_000


def _ctx(programs, counted, family=FAMILY):
    """``programs``: (start ms, length ms, loops inside, ms of scan calls
    inside) each; ``counted``: {window: chunks} the program's counter grew
    by over the run."""
    modules, ops = [], [["fusion.1", 0, 10]]
    for start, dur, loops, scan in programs:
        modules.append(["jit_chunk_prefill(123)", start * MS, dur * MS])
        for i in range(loops):
            ops.append([f"while.{i}", start * MS + (1 + 4 * i) * MS, 3 * MS])
        if scan:
            ops.append(["ssm_chunk_scan.3", start * MS + MS, scan * MS])
    # A decode tick between them: neither its loops nor a stray kernel
    # call outside every chunk program count.
    modules.append(["jit_decode_tick(9)", 500 * MS, 70 * MS])
    ops.append(["while.7", 501 * MS, 60 * MS])
    ops.append(["ssm_chunk_scan.9", 600 * MS, 5 * MS])
    dev = {"modules": modules, "ops": ops}
    after = "".join(
        f'dllm_prefill_chunks_by_window_total{{tier="nano",window="{w}"}} '
        f'{n + 5.0}\n' for w, n in counted.items())
    before = "".join(
        f'dllm_prefill_chunks_by_window_total{{tier="nano",window="{w}"}} '
        f'5.0\n' for w in counted)
    return types.SimpleNamespace(
        served=types.SimpleNamespace(entries={"nano": {
            "family": family, "model": model(), "tier": {}}}),
        trace={"t_lo": 0, "t_hi": 1000 * MS}, peaks={
            "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        metrics_before=before, metrics_after=after,
        tier_traces=lambda tier: [dev])


def test_readers_read_the_chunk_programs_of_a_trace():
    from layer_metrics import ssm_attention_mlp_readers as readers
    fam, m = mf.load_family(FAMILY), model()
    ctx = _ctx([(100, 20, 1, 6), (200, 24, 1, 7), (300, 22, 1, 6.5)],
               {1024: 3, 16384: 32})
    # 19.5 ms of scan calls inside 66 ms of chunk programs.
    assert readers.chunk_scan_share_of_chunk_ms(ctx, "nano") == \
        pytest.approx(100 * 19.5 / 66)
    flops = (3 * fam.chunk_flops_per_chip(m, 256, 1024)
             + 32 * fam.chunk_flops_per_chip(m, 256, 16384)) / 35
    assert readers.chunk_mfu(ctx, "nano") == pytest.approx(
        100 * flops / 197e12 / 22e-3)
    assert 30 < readers.chunk_mfu(ctx, "nano") < 40
    # A program with another count of loops than the family's is left
    # out; with none left, nothing is read.
    assert readers.chunk_mfu(_ctx([(100, 20, 2, 6)], {1024: 1}),
                             "nano") is None
    # A program without the counter (the commits before this family), a
    # trace without chunks, a tier of another family: nothing to read.
    assert readers.chunk_mfu(_ctx([(100, 20, 1, 6)], {}), "nano") is None
    assert readers.chunk_scan_share_of_chunk_ms(_ctx([], {}), "nano") is None
    other = _ctx([(100, 20, 1, 6)], {1024: 1},
                 family="ssm_window_shared_kv_decoder")
    other.served.entries["nano"]["model"] = json.load(open(os.path.join(
        HERE, "configs", "phi-4-mini-flash-reasoning.json")))
    assert readers.chunk_mfu(other, "nano") is None
    assert readers.chunk_scan_share_of_chunk_ms(other, "nano") is None
    assert readers.decode_hbm_share_ssm_attention(other, "nano") is None
