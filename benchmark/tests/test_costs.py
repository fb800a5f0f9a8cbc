"""costs.py against hand-worked sizes for two models (CPU, by hand:
``python3 -m pytest benchmark/tests -q``)."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import costs  # noqa: E402


def model(config):
    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        return json.load(f)


def test_smollm2_weights_and_kv():
    m = model("smollm2-1.7b")
    # q, k, v, o: 4 x 2048 x 2048 = 16 777 216; MLP 3 x 2048 x 8192 =
    # 50 331 648; a layer 67 108 864; 24 layers 1 610 612 736; embedding
    # 49152 x 2048 = 100 663 296.
    assert costs.layer_params(m) == 67_108_864
    assert costs.embed_params(m) == 100_663_296
    assert costs.weight_bytes_per_chip(m) == 2 * (1_610_612_736
                                                  + 100_663_296)
    # 2 (K, V) x 24 layers x 32 heads x 64 x 2 B = 196 608 B a token.
    assert costs.kv_bytes_per_token(m) == 196_608


# Mistral-7B-v0.3's published sizes: grouped-query attention and a tier of
# two chips, which SmolLM2 does not exercise.
MISTRAL_7B = {"hidden_size": 4096, "num_hidden_layers": 32,
              "num_attention_heads": 32, "num_key_value_heads": 8,
              "head_dim": 128, "intermediate_size": 14336,
              "vocab_size": 32768, "torch_dtype": "bfloat16"}


def test_mistral_weights_and_kv_at_tp2():
    m = MISTRAL_7B
    # q, o: 2 x 4096 x 4096 = 33 554 432; k, v: 2 x 4096 x 1024 =
    # 8 388 608; MLP 3 x 4096 x 14336 = 176 160 768; layer 218 103 808;
    # 32 layers 6 979 321 856; embedding 32768 x 4096 = 134 217 728.
    assert costs.layer_params(m) == 218_103_808
    assert costs.weight_bytes_per_chip(m, tp=2) == 2 * (
        6_979_321_856 // 2 + 134_217_728)
    # 2 x 32 layers x 8 heads x 128 x 2 B = 131 072 B a token.
    assert costs.kv_bytes_per_token(m) == 131_072


def test_decode_step_bytes_adds_each_context():
    m = model("smollm2-1.7b")
    w = costs.weight_bytes_per_chip(m)
    assert costs.decode_step_bytes_per_chip(m, [100, 200]) == \
        w + 300 * 196_608
    assert costs.decode_step_bytes_per_chip(m, [100, 200], tp=2) == \
        costs.weight_bytes_per_chip(m, 2) + 300 * 196_608 / 2
