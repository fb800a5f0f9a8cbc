"""The latent-attention, routed-expert, multi-stream family
(models/latent_moe.py) against its plain float32 reference
(benchmark/reference/latent_moe_decoder.py, loaded by path: it imports
nothing of the program), at the tiny ``latent_test`` preset: hidden 64,
4 heads of 16 + 8, latent 32/24, 8 experts 2 a token + a shared one, 4
streams, 1 dense + 2 expert layers.  Every tolerance carries its reason.
"""

import dataclasses
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu import models
from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
from distributed_llm_tpu.engine import paged_kv
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.models import latent_moe, transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The published keys of the tiny preset, as the reference reads them.
TINY = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 512,
    "max_position_embeddings": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "q_lora_rank": 32, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_scaling": {"type": "yarn", "factor": 4.0,
                     "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                     "mscale_all_dim": 1.0},
    "first_k_dense_replace": 1, "n_routed_experts": 8,
    "moe_intermediate_size": 32, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "scoring_func": "sigmoid",
    "routed_scaling_factor": 2.0, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "tie_word_embeddings": False, "torch_dtype": "float32",
}
SEED = 3


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "latent_moe_reference",
        os.path.join(ROOT, "benchmark", "reference", "latent_moe_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(dtype="float32", **kw):
    return dataclasses.replace(MODEL_PRESETS["latent_test"], dtype=dtype,
                               **kw)


def _params(cfg, seed=SEED):
    # The seed as an argument of the jitted maker, as the engine makes them.
    return jax.jit(lambda s: models.init_params(cfg, s))(jnp.int32(seed))


def _layer(cfg, moe=True, seed=SEED):
    return latent_moe.init_layer(cfg, jax.random.PRNGKey(seed), moe)


def _serve(cfg, params, tok, n_prompt, chunk=32):
    """Chunked prefill of ``tok[:n_prompt]`` then teacher-forced decode of
    the rest through a latent pool; logits at positions n_prompt-1 ..."""
    pcfg = paged_kv.PagedConfig(block_size=16, max_slots=2, max_seq_len=128)
    pool = paged_kv.init_pool(cfg, pcfg)
    # The row's 32 numbers rest at one whole lane-width (ISSUE 57).
    assert set(pool) == {"c"} and pool["c"].shape[-1] == 128
    table = jnp.arange(1, 9, dtype=jnp.int32)
    for start in range(0, n_prompt, chunk):
        piece = np.zeros((1, chunk), np.int32)
        k = min(chunk, n_prompt - start)
        piece[0, :k] = tok[start:start + k]
        hidden, pool = paged_kv.chunk_prefill_paged(
            cfg, params, jnp.asarray(piece), jnp.array([start]),
            jnp.array([n_prompt]), pool, table, 64)
    out = [transformer.logits_from_hidden(params, hidden[0, k - 1])]
    tables = jnp.stack([table, jnp.zeros(8, jnp.int32)])
    for p in range(n_prompt, len(tok)):
        logits, pool = paged_kv.decode_step_paged(
            cfg, params, jnp.asarray([tok[p], 0]), jnp.array([p, 0]), pool,
            tables)
        out.append(logits[0])
    return np.stack([np.asarray(x, np.float32) for x in out])


# (1) -------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_matches_reference(ref, dtype):
    cfg = _cfg(dtype)
    tok = np.random.default_rng(0).integers(0, 500, 48).astype(np.int32)
    got = _serve(cfg, _params(cfg), tok, n_prompt=40)
    model = dict(TINY, torch_dtype=dtype)
    want = np.asarray(ref.logits(
        model, ref.init_weights(model, SEED), jnp.asarray(tok[None]),
        jnp.arange(39, 48)[None]))[0]
    if dtype == "float32":
        # Same numbers, another order of summation (absorbed products,
        # a cache, grouped experts): logits of size 0.7 agree to 1e-4
        # with three decimal orders to spare.
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    else:
        # bfloat16 keeps 8 bits: every rounding is 2^-9 = 0.2% of its
        # value; through 3 layers the logits stay within 2% in norm
        # (0.4% seen).  A float32 answer would read 1e-7 here, a wrong
        # block several tens of percent.
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert 1e-5 < err < 2e-2


# (2) -------------------------------------------------------------------------

def test_absorbed_decode_equals_naive_attention_on_the_same_cache():
    cfg = _cfg()
    params = _params(cfg)
    pcfg = paged_kv.PagedConfig(block_size=16, max_slots=2, max_seq_len=128)
    pool = paged_kv.init_pool(cfg, pcfg)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    tok = np.random.default_rng(1).integers(0, 500, (1, 32)).astype(np.int32)
    _, pool = paged_kv.chunk_prefill_paged(
        cfg, params, jnp.asarray(tok), jnp.array([0]), jnp.array([32]),
        pool, table, 64)
    args = (cfg, params, jnp.array([[7], [9]]), jnp.array([[32], [0]]),
            jnp.array([[32], [0]]), pool, jnp.array([[3], [0]]),
            jnp.array([[0], [0]]), jnp.stack([table, jnp.zeros(8, jnp.int32)]))
    absorbed, _, _ = latent_moe.forward_paged(*args, absorbed=True)
    naive, _, _ = latent_moe.forward_paged(*args, absorbed=False)
    # Algebraically equal; float32 products in another order: 1e-5 of
    # hidden states of size 1.
    np.testing.assert_allclose(np.asarray(absorbed[0]), np.asarray(naive[0]),
                               atol=1e-5, rtol=0)


# (3) -------------------------------------------------------------------------

def test_routing_is_dropless_and_the_bias_moves_the_choice_only():
    cfg = _cfg()
    lp = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 64), jnp.float32)
    choice, weight = latent_moe.route(cfg, lp, x)
    s = jax.nn.sigmoid(x @ lp["router"])
    assert choice.shape == (24, 2)
    assert (np.asarray(choice[:, 0]) != np.asarray(choice[:, 1])).all()
    # Weights: the chosen experts' scores, normalised, times the scale;
    # the bias is not in them (float32 throughout: 1e-6).
    picked = np.take_along_axis(np.asarray(s), np.asarray(choice), 1)
    np.testing.assert_allclose(
        np.asarray(weight), picked / picked.sum(1, keepdims=True) * 2.0,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weight).sum(1), 2.0, rtol=1e-6)
    # A bias that favours experts 2 and 5 by more than any score (scores
    # are in (0, 1)) sends EVERY token to those two: nothing is dropped.
    forced = dict(lp, router_bias=jnp.zeros(8).at[jnp.array([2, 5])].set(9.0))
    choice_f, weight_f = latent_moe.route(cfg, forced, x)
    assert sorted(set(np.asarray(choice_f).ravel())) == [2, 5]
    picked = np.asarray(s)[:, [2, 5]]
    np.testing.assert_allclose(
        np.sort(np.asarray(weight_f), 1),
        np.sort(picked / picked.sum(1, keepdims=True) * 2.0, 1), rtol=1e-6)
    out, counts = latent_moe.routed_experts(cfg, forced, x)
    assert np.asarray(counts).tolist() == [0, 0, 24, 0, 0, 24, 0, 0]

    def expert(e):
        return (jax.nn.silu(x @ lp["we_gate"][e]) * (x @ lp["we_up"][e])
                ) @ lp["we_down"][e]
    w = {int(e): np.asarray(weight_f)[np.asarray(choice_f) == e]
         for e in (2, 5)}
    want = w[2][:, None] * expert(2) + w[5][:, None] * expert(5)
    # All 24 tokens through the same two experts, each computed: equal to
    # the plain products to float32 rounding.
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


# (4) -------------------------------------------------------------------------

def test_expert_layer_adds_the_shared_expert_once_to_the_routed_sum(ref):
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 64), jnp.float32)
    lp = _layer(cfg)
    routed, counts = latent_moe.routed_experts(cfg, lp, x)
    assert int(counts.sum()) == 16 * 2
    shared = transformer._swiglu(x, lp["ws_gate"], lp["ws_up"],
                                 lp["ws_down"])
    w = ref.make_layer(TINY, jax.random.PRNGKey(SEED), True)
    want = ref._experts(TINY, w, x)
    # The routed sum and the shared expert ONCE are the reference's layer
    # (every expert computed, gated); float32, sums in another order: 1e-5.
    np.testing.assert_allclose(np.asarray(routed) + np.asarray(shared),
                               np.asarray(want), atol=1e-5)
    full, _ = latent_moe._ffn(cfg, lp, x[None], True)
    np.testing.assert_allclose(np.asarray(full[0]), np.asarray(want),
                               atol=1e-5)


def test_router_reads_the_float32_input_of_a_bfloat16_model():
    """Three experts score 1 + 1, 2, 3 x 2^-10 on a token: float32 tells
    them apart and picks the last two; rounded to bfloat16 (8 bits) all
    three read 1 and the tie goes to the first two.  The block hands the
    router its float32 normed input, so a bfloat16 model chooses as the
    float32 reference does."""
    cfg = _cfg("bfloat16")
    lp = _layer(cfg)
    lp["router"] = jnp.zeros((64, 8), jnp.bfloat16).at[
        jnp.arange(3), jnp.arange(3)].set(1.0)
    lp["router_bias"] = jnp.zeros(8, jnp.float32)
    x = jnp.zeros((5, 64), jnp.float32).at[:, :3].set(
        1.0 + 2.0 ** -10 * jnp.arange(1, 4, dtype=jnp.float32))
    _, counts = latent_moe.routed_experts(cfg, lp, x)
    assert np.asarray(counts).tolist() == [0, 5, 5, 0, 0, 0, 0, 0]
    _, rounded = latent_moe.routed_experts(cfg, lp, x.astype(jnp.bfloat16))
    assert np.asarray(rounded).tolist() == [5, 5, 0, 0, 0, 0, 0, 0]
    # Through the FFN sublayer as the block calls it (float32 in).
    out, counts = latent_moe._ffn(cfg, lp, x[None], True)
    assert np.asarray(counts).tolist() == [0, 5, 5, 0, 0, 0, 0, 0]
    assert out.dtype == jnp.bfloat16


# (5) -------------------------------------------------------------------------

def test_stream_maps_are_doubly_stochastic_and_equal_the_reference(ref):
    cfg = _cfg()
    hc = latent_moe._hc_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (10, 4, 64), jnp.float32)
    pre, post, res = latent_moe.stream_maps(cfg, hc, x)
    res = np.moveaxis(np.asarray(res), -1, 0)                 # [T, n, n]
    # 20 Sinkhorn rounds on 4 x 4 positive matrices: the last column
    # pass makes columns exact (up to hc_eps), rows converge to 1e-3.
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert np.abs(res - np.eye(4)).max() > 0.3     # far from the identity
    # The whole sublayer against the reference's, with F = 3u + 1.
    f = lambda u: (3.0 * u + 1.0, None)
    got, _ = latent_moe._hyper(cfg, hc, x[None], f)
    want = ref._hyper(TINY, hc, x, lambda u: 3.0 * u + 1.0)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-5)           # float32, same formula


# (6) -------------------------------------------------------------------------

def test_yarn_frequencies_and_softmax_scale_follow_the_formula():
    cfg = _cfg()
    dim, theta, factor, orig = 8, 10000.0, 4.0, 64
    plain = theta ** (-np.arange(0, dim, 2) / dim)

    def pair_at(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_at(32)), 0)
    high = min(math.ceil(pair_at(1)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = plain / factor * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(np.asarray(latent_moe.yarn_inv_freq(cfg)),
                               want, rtol=1e-6)       # float32 arithmetic
    assert want[0] == plain[0] and want[-1] == plain[-1] / factor
    assert latent_moe.softmax_scale(cfg) == pytest.approx(
        24 ** -0.5 * (0.1 * math.log(4.0) + 1.0) ** 2)
    # The published keys: 192^-0.5 (0.1 ln 64 + 1)^2, sin/cos unscaled.
    big = _cfg(qk_nope_head_dim=128, qk_rope_head_dim=64, rope_factor=64.0,
               rope_original_max_pos=4096)
    assert latent_moe.softmax_scale(big) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64.0) + 1.0) ** 2)
    sin, cos = latent_moe.rope_sincos(big, jnp.array([0]))
    np.testing.assert_array_equal(np.asarray(cos), 1.0)
    plain_cfg = _cfg(rope_factor=1.0)
    np.testing.assert_allclose(
        np.asarray(latent_moe.yarn_inv_freq(plain_cfg)), plain, rtol=1e-6)
    assert latent_moe.softmax_scale(plain_cfg) == pytest.approx(24 ** -0.5)


# (7), (8), (9): the engine ---------------------------------------------------

def _while_depth(hlo: str) -> int:
    """How deep ``while`` loops nest in a compiled module's text, through
    every computation a line names (a conditional's branches too)."""
    bodies = {}
    for m in re.finditer(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}",
                         hlo, re.M | re.S):
        bodies[m.group(1)] = m.group(2)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo, re.M).group(1)

    def depth(name, seen=()):
        if name not in bodies or name in seen:
            return 0
        best = 0
        for line in bodies[name].splitlines():
            loop = " while(" in line
            for group in re.findall(
                    r"(?:body|condition|calls|to_apply|true_computation|"
                    r"false_computation|branch_computations)="
                    r"(\{[^}]*\}|%?[\w.\-]+)", line):
                for ref_ in re.findall(r"%?([\w.\-]+)", group):
                    best = max(best, depth(ref_, seen + (name,))
                               + (1 if loop else 0))
        return best
    return depth(entry)


@pytest.fixture(scope="module")
def engine():
    MODEL_PRESETS["latent_test_f32"] = _cfg("float32",
                                            name="latent_test_f32")
    tier = TierConfig(name="nano", model_preset="latent_test_f32",
                      decode_batch=4, max_new_tokens=8, kv_block_size=16,
                      prefill_buckets=(16, 32, 64, 128),
                      prefill_chunk_tokens=16, decode_steps_per_tick=4)
    eng = ContinuousBatchingEngine(tier, seed=SEED)
    yield eng
    eng.stop()
    del MODEL_PRESETS["latent_test_f32"]


def test_tick_nests_two_whiles_and_the_chunk_program_one(engine):
    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tick = engine._decode_step().lower(
        engine.params, engine.pool, i32(4, 2), i32(4), i32(4),
        jax.ShapeDtypeStruct((4,), jnp.float32), key).compile()
    assert engine._decode_step().__name__ == "decode_tick"
    # Steps of a tick, layers of a step — and nothing inside a layer.
    assert _while_depth(tick.as_text()) == 2
    chunk = engine._chunk_prefill_fn(16, 128).lower(
        engine.params, engine.pool, i32(1, 16), i32(1), i32(1), i32(16),
        key, jax.ShapeDtypeStruct((), jnp.float32)).compile()
    assert _while_depth(chunk.as_text()) == 1


def test_engine_generates_the_references_greedy_tokens(engine, ref):
    first = "a document of some length to read and think about, "
    prompt = first + "and then a question?"
    engine.generate(first, max_new_tokens=4)
    hits = engine.prefix_cache.stats()["hits"]
    before = engine.moe_stats()
    assert engine.decode_attention_form() == "latent"
    got = engine.generate(prompt, max_new_tokens=8)
    # The second prompt extends the parked first: copy_block + suffix chunk.
    assert engine.prefix_cache.stats()["hits"] == hits + 1
    ids = engine.tokenizer.encode(prompt)
    if ids[0] != engine.tokenizer.bos_id:
        ids = [engine.tokenizer.bos_id] + list(ids)
    assert got.prompt_tokens == len(ids)
    # Greedy loop over the float32 reference, full forward each step,
    # right-padded to one shape.  Float32 on both sides: the logits agree
    # to 1e-6, so the argmax does unless two logits tie that closely.
    weights = ref.init_weights(TINY, SEED)
    seq = np.zeros((1, len(ids) + 8), np.int32)
    seq[0, :len(ids)] = ids
    want = []
    for p in range(len(ids), len(ids) + 8):
        logits = ref.logits(TINY, weights, jnp.asarray(seq),
                            jnp.array([[p - 1]]))
        seq[0, p] = int(np.argmax(np.asarray(logits[0, 0])))
        want.append(int(seq[0, p]))
    assert list(got.token_ids) == want

    # (9) every step of every tick counts slots x k x expert layers.
    after = engine.moe_stats()
    steps = after["steps"]["decode"] - before["steps"]["decode"]
    assert steps >= 8
    tokens = (np.asarray(after["expert_tokens"]["decode"]).sum()
              - np.asarray(before["expert_tokens"]["decode"]).sum())
    assert tokens == steps * 4 * 2 * 2
    assert np.asarray(after["expert_tokens"]["decode"]).shape == (2, 8)
    assert 2 <= (after["experts_touched"]["decode"]
                 - before["experts_touched"]["decode"]) / (steps * 2) <= 8
    assert after["steps"]["prefill"] > before["steps"]["prefill"]


# (10) ------------------------------------------------------------------------

@pytest.mark.parametrize("what,kw", [
    ("kv_quantize", dict(kv_quantize="int8")),
    ("draft_preset", dict(draft_preset="draft_test")),
    ("host_kv_bytes", dict(host_kv_bytes=1 << 20)),
    ("tensor-parallel", dict(tp=2)),
])
def test_unsupported_combinations_raise_by_name(what, kw):
    tier = TierConfig(name="nano", model_preset="latent_test",
                      decode_batch=2, kv_block_size=16,
                      prefill_buckets=(16, 32, 64, 128), **kw)
    mesh = None
    if "tp" in kw:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="latent-attention family") as e:
        ContinuousBatchingEngine(tier, seed=0, mesh=mesh)
    assert what in str(e.value)
    with pytest.raises(ValueError, match="latent-attention family"):
        paged_kv.init_pool(MODEL_PRESETS["latent_test"],
                           paged_kv.PagedConfig(), "int8")


def test_pool_programs_work_on_whatever_arrays_the_pool_has():
    cfg = _cfg()
    pool = paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=16, max_slots=1, max_seq_len=64))
    rows = jax.random.normal(jax.random.PRNGKey(0), (3, 32, 128), jnp.float32)
    pool = paged_kv.write_prefill_blocks(pool, jnp.array([2, 4]), rows)
    np.testing.assert_array_equal(np.asarray(pool["c"][:, 4]),
                                  np.asarray(rows[:, 16:]))
    pool = paged_kv.copy_block(pool, jnp.int32(4), jnp.int32(1))
    tiles = paged_kv.gather_blocks(pool, jnp.array([1, 2]))
    assert tiles["c"].shape == (3, 2, 16, 128)
    pool = paged_kv.scatter_blocks(pool, jnp.array([3, 0]), tiles)
    np.testing.assert_array_equal(np.asarray(pool["c"][:, 3]),
                                  np.asarray(rows[:, 16:]))
    assert paged_kv.pool_block_bytes(cfg, 16) == 3 * 16 * 128 * 4
    # The latent row, the one row that is not heads by head_dim wide,
    # rests at whole lane-widths; a K/V row rests as wide as it is.
    assert (cfg.cache_row_width, cfg.cache_row_rest_width) == (32, 128)
    nano = MODEL_PRESETS["nano_test"]
    assert nano.cache_row_width == nano.cache_row_rest_width == 2 * 16


def test_int8_weights_reach_the_familys_matrices():
    from distributed_llm_tpu.ops.quant import is_quantized, quantize_params
    cfg = _cfg()
    params = _params(cfg)
    q = jax.jit(quantize_params)(params)
    assert is_quantized(q["head"]) and is_quantized(q["embed"])
    for group, keys in (("lead", ("w_qa", "w_kvb", "wo", "w_gate")),
                        ("layers", ("w_qb", "w_kva", "ws_up", "we_gate",
                                    "we_down"))):
        assert all(is_quantized(q[group][k]) for k in keys)
    assert not is_quantized(q["layers"]["router"])
    assert not is_quantized(q["layers"]["hc_ffn"]["phi"])
    tok = np.random.default_rng(2).integers(0, 500, 40).astype(np.int32)
    full = _serve(cfg, params, tok, n_prompt=36)
    low = _serve(cfg, q, tok, n_prompt=36)
    err = np.linalg.norm(low - full) / np.linalg.norm(full)
    # int8 keeps 7 bits a weight: percent-level logits, never float32's
    # 1e-7 and never a wrong block's tens of percent.
    assert 1e-4 < err < 0.1
