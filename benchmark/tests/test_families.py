"""A model family is files: ``families/<family>.py`` (the program's
ModelConfig and the byte counts), ``reference/<family>.py`` and a
configuration, all found by the tier's ``family``.  The dense family gives
what ``cluster.model_config`` and ``costs.py`` gave before the lookup
(values written out), and a SECOND family made of files in a temporary
directory is built, warmed and put through ``correct.engine_statistic``
with no file under ``benchmark/`` edited.  (CPU, by hand:
``python3 -m pytest benchmark/tests -q``.)"""
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]
import cluster  # noqa: E402
import correct  # noqa: E402
import costs  # noqa: E402
import manifest as mf  # noqa: E402


def smollm2():
    with open(os.path.join(HERE, "configs", "smollm2-1.7b.json")) as f:
        return json.load(f)


# What cluster.model_config returned at the parent of the PR that moved it
# (PR 28), published and rehearsal sizes; the program's defaults are not
# written out, a field the family sets is.
PUBLISHED = dict(name="bench_smollm2_1p7b", tokenizer="byte",
                 vocab_size=49152, hidden_size=2048, num_layers=24,
                 num_heads=32, num_kv_heads=32, ffn_size=8192,
                 max_seq_len=8192, rope_theta=130000.0, norm_eps=1e-05,
                 dtype="bfloat16")
REHEARSAL = dict(PUBLISHED, name="bench_smollm2_1p7b_rehearsal",
                 vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                 num_kv_heads=4, ffn_size=128)


@pytest.mark.parametrize("rehearsal,want,head_dim",
                         [(False, PUBLISHED, 64), (True, REHEARSAL, 16)])
def test_dense_family_gives_the_model_config_it_gave(rehearsal, want,
                                                     head_dim):
    from distributed_llm_tpu.config import ModelConfig
    e = cluster.tier_entries(smollm2(), rehearsal)["nano"]
    assert e["family"] == "dense_decoder"
    assert e["model"]["head_dim"] == head_dim
    cfg = cluster.program_config(e)
    assert cfg == ModelConfig(**want)
    assert cluster.model_config(e["preset"], e["model"]) == cfg   # the alias
    with pytest.raises(ValueError, match="head_dim"):
        cluster.program_config(dict(e, model=dict(e["model"], head_dim=48)))


TOY_FAMILY = '''
"""A second family: the dense one's keys plus ``n_routed_experts``, which
the dense family ignores."""
from costs import BYTES


def model_config(preset, model):
    from distributed_llm_tpu.config import ModelConfig
    return ModelConfig(
        name=preset, tokenizer="byte", vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        ffn_size=model["moe_intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        num_experts=model["n_routed_experts"])


def rehearsal_model(model, sizes):
    return {**model, **sizes}


def weight_bytes_per_chip(model, tp=1):
    return 2 * model["n_routed_experts"] * model["moe_intermediate_size"]


def kv_bytes_per_token(model):
    return 7 * BYTES["bfloat16"]


def decode_step_bytes_per_chip(model, contexts, tp=1):
    return weight_bytes_per_chip(model, tp) + sum(contexts) * 14
'''

# The toy's reference is the dense forward pass read from its file: the
# statistic only has to be a finite number here, not under a limit.
TOY_REFERENCE = '''
import importlib.util
_spec = importlib.util.spec_from_file_location("_dense_ref", {dense!r})
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)


def init_weights(model, seed, sharding=None):
    return _dense.init_weights(dict(model, intermediate_size=model[
        "moe_intermediate_size"]), seed, sharding)


def logits(model, weights, tokens, keep):
    return _dense.logits(model, weights, tokens, keep)
'''


@pytest.fixture
def toy_tree(tmp_path, monkeypatch):
    """A benchmark directory that holds ONLY the second family's files;
    the harness looks everything up under ``manifest.BENCH_DIR``."""
    for d in ("families", "reference", "configs"):
        (tmp_path / d).mkdir()
    (tmp_path / "families" / "toy_moe.py").write_text(TOY_FAMILY)
    (tmp_path / "reference" / "toy_moe.py").write_text(TOY_REFERENCE.format(
        dense=os.path.join(HERE, "reference", "dense_decoder.py")))
    tier = dict(smollm2()["tiers"]["nano"], family="toy_moe",
                preset="bench_toy_moe",
                rehearsal_model={"max_position_embeddings": 2048})
    tier["rehearsal_tier"] = {"max_new_tokens": 8, "kv_pool_blocks": 48}
    config = {"hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "moe_intermediate_size": 96, "n_routed_experts": 4,
              "vocab_size": 1024, "max_position_embeddings": 4096,
              "tiers": {"nano": tier}, "router": smollm2()["router"],
              "correct": {"limit": {"nano": 1.0},
                          "sample": {"lengths": [600, 840], "n_decode": 4},
                          "controls": ["int8_weights"]}}
    (tmp_path / "configs" / "toy-moe.json").write_text(json.dumps(config))
    monkeypatch.setattr(mf, "BENCH_DIR", str(tmp_path))
    return tmp_path


def test_second_family_is_served_from_added_files_alone(toy_tree):
    import jax
    config = mf.load_json("configs", "toy-moe.json")
    assert correct.sample_sizes(config) == ((600, 840), 4)
    assert correct.controls_of(config) == ("int8_weights",)
    served = cluster.build(config, seed=2147483659, rehearsal=True,
                           devices=jax.devices()[:1])
    try:
        e = served.entries["nano"]
        engine = served.engine("nano")
        assert e["model"]["max_position_embeddings"] == 2048
        assert "head_dim" not in e["model"]       # the dense family's rule
        assert engine.cfg.num_experts == config["n_routed_experts"] == 4
        assert engine.cfg.ffn_size == 96 and engine.cfg.max_seq_len == 2048
        cluster.warm_shapes(served, "nano", [40])
        stat = correct.engine_statistic(
            engine, e["family"], e["model"], 2147483659,
            sample=correct.sample_sizes(config))
        assert stat["finite"] and math.isfinite(stat["rel_err"])
        assert stat["positions"] == 2 * 5 and stat["narrow"] == []
        assert costs.decode_step_bytes_per_chip(
            e["model"], [10, 20], family=e["family"]) == 2 * 4 * 96 + 30 * 14
    finally:
        served.drain()


def test_unknown_family_lists_what_was_looked_for(capsys):
    config = smollm2()
    config["tiers"]["nano"]["family"] = "latent_moe"
    for look in (lambda: cluster.tier_entries(config, True),
                 lambda: cluster.program_config(
                     cluster.tier_entries(config, False)["nano"]),
                 lambda: costs.kv_bytes_per_token(config,
                                                  family="latent_moe"),
                 lambda: mf.load_module("reference", "latent_moe")):
        with pytest.raises(mf.ManifestError) as err:
            look()
        assert err.value.code == 2
        said = capsys.readouterr().err
        assert "latent_moe.py" in said and "dense_decoder" in said


def test_family_file_that_lacks_a_name_is_refused(toy_tree, capsys):
    (toy_tree / "families" / "half.py").write_text(
        "def model_config(preset, model):\n    return None\n")
    with pytest.raises(mf.ManifestError):
        mf.load_family("half")
    said = capsys.readouterr().err
    assert "rehearsal_model" in said and "decode_step_bytes_per_chip" in said


def test_sample_is_the_configurations_and_todays_where_it_states_none():
    config = smollm2()
    assert "sample" not in config["correct"]
    assert correct.sample_sizes(config) == ((616, 808, 1064, 1288), 16)
    seqs = correct.draw_sample(2147483659, 49152)
    assert [len(s) for s in seqs] == [616, 808, 1064, 1288]
    assert [s[:6].tolist() for s in seqs] == [
        [20850, 25970, 40974, 16275, 38997, 45823],
        [6561, 37152, 27045, 26890, 44022, 37965],
        [12912, 18494, 13204, 8572, 48273, 41529],
        [19662, 12588, 11758, 7380, 16185, 49018]]
    assert [int(s.sum()) for s in seqs] == [15398003, 19952296, 25705010,
                                            30677492]
    config["correct"]["sample"] = {"lengths": [8200, 16400], "n_decode": 8}
    lengths, n_decode = correct.sample_sizes(config)
    long = correct.draw_sample(2147483659, 49152, lengths)
    assert [len(s) for s in long] == [8200, 16400] and n_decode == 8
    assert long[0][:616].tolist() == seqs[0].tolist()    # one stream of ids
    assert correct.kept_positions(long, n_decode)[1].tolist() == list(
        range(16400 - 9, 16400))
    for bad in ({"lengths": [8]}, {"n_decode": 0}, {"length": [900]}):
        config["correct"]["sample"] = bad
        with pytest.raises(mf.ManifestError):
            correct.sample_sizes(config)


def test_controls_are_the_configurations():
    config = smollm2()
    assert correct.controls_of(config) == ("int8_weights", "int8_kv")
    config["correct"]["controls"] = ["int8_kv"]
    assert correct.controls_of(config) == ("int8_kv",)
    config["correct"]["controls"] = []
    assert correct.controls_of(config) == ()     # its `control` says why
    config["correct"]["control"] = " "
    with pytest.raises(mf.ManifestError):
        correct.controls_of(config)
    config["correct"]["controls"] = ["int4_weights"]
    with pytest.raises(mf.ManifestError):
        correct.controls_of(config)


def test_byte_counts_through_the_lookup_are_the_hand_worked_ones():
    # The sizes of tests/test_costs.py, through families/dense_decoder.py.
    fam = mf.load_family("dense_decoder")
    m = smollm2()
    assert fam.layer_params(m) == 67_108_864
    assert fam.embed_params(m) == 100_663_296
    assert fam.weight_bytes_per_chip(m) == 2 * (1_610_612_736 + 100_663_296)
    assert fam.kv_bytes_per_token(m) == 196_608
    assert fam.decode_step_bytes_per_chip(m, [100, 200], 2) == \
        2 * (1_610_612_736 // 2 + 100_663_296) + 300 * 196_608 / 2
    for name in ("weight_bytes_per_chip", "kv_bytes_per_token"):
        assert getattr(costs, name)(m, family="dense_decoder") == \
            getattr(costs, name)(m) == getattr(fam, name)(m)
    assert costs.decode_step_bytes_per_chip(
        m, [100, 200], family="dense_decoder") == \
        fam.weight_bytes_per_chip(m) + 300 * 196_608


STEP_MS = 39.211611625


def hbm_share(family, model):
    """``decode_hbm_share`` over the recorded stretch of decode-closed
    (tests/test_tracing.py: two ticks, ``STEP_MS`` a step), read as a tier of
    ``family`` with two requests of 120 positions in flight."""
    import types
    from layer_metrics import trace_readers
    with open(os.path.join(HERE, "tests", "data",
                           "trace_decode_closed.json")) as f:
        trace = json.load(f)
    records = [{"device": "nano", "stamps": [4.0] * 20, "sent": 1.0,
                "end": 9.0, "prompt_tokens": 100}] * 2
    entry = {"family": family, "model": model, "tier": {"tp": 1}}
    ctx = types.SimpleNamespace(
        tier_traces=lambda tier: [trace["devices"]["0"]],
        served=types.SimpleNamespace(entries={"nano": entry}),
        records=records, host_span=(4.0, 6.0),
        peaks={"hbm_bytes_per_s": 819e9})
    return trace_readers.decode_hbm_share(ctx, "nano")


def test_decode_hbm_share_counts_the_dense_familys_bytes(capsys):
    need = mf.load_family("dense_decoder").weight_bytes_per_chip(
        smollm2()) + 240 * 196_608
    got = hbm_share("dense_decoder", smollm2())
    assert abs(got - 100.0 * need / 819e9 / (STEP_MS / 1e3)) < 1e-9 * got
    assert repr(float(need)) in capsys.readouterr().out   # printed, as read


def test_decode_hbm_share_counts_the_second_familys_bytes(toy_tree):
    got = hbm_share("toy_moe", mf.load_json("configs", "toy-moe.json"))
    want = 100.0 * (2 * 4 * 96 + 240 * 14) / 819e9 / (STEP_MS / 1e3)
    assert abs(got - want) < 1e-9 * want
