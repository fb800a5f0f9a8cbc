"""The hybrid family's fifth pattern (models/hybrid_ssm.py: latent
attention UNDER A ROTARY TERM, YaRN's, in every layer — a paged latent row
a token and no recurrent row at all — a dense lead MLP ahead of the one
layer loop, sigmoid-routed gated experts of which this share holds a
quarter, an untied head: Sarvam's ``sarvam_mla`` block) against its plain
float32 reference (benchmark/reference/rotary_latent_moe_decoder.py, which
imports nothing of the program), at a tiny preset this file registers:
"L-" and two periods of "LE", positions past the tiny
``original_max_position_embeddings`` so that YaRN's ramp and its magnitude
are in play.

(1) system against reference on logits, prefill in chunks then decode
through the latent pool; (2) each named part of the mathematics dropped in
turn from the reference fails the tolerance by a stated multiple; (3) the
four shares add up to the uncut layer; (4) a pattern with no row kind:
its pool, two interleaved sequences through the engine, every block freed;
(5) what ``hybrid_ssm.check`` passes and refuses, by name; (6) the
pattern without rotary (kimi's) computes what it did before ``L`` could
rotate, bit for bit; then the programs, the route and /stats, the
roofline, int8 and the benchmark family's mapping.
"""

import dataclasses
import importlib.util
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu import models
from distributed_llm_tpu.config import (MODEL_PRESETS, ModelConfig,
                                        TierConfig)
from distributed_llm_tpu.engine import paged_kv
from distributed_llm_tpu.engine.batching import ContinuousBatchingEngine
from distributed_llm_tpu.models import hybrid_ssm, latent_moe, transformer
from test_latent_moe import _while_depth

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

# The keys of the tiny preset, as the reference and the benchmark's family
# file read them: the published names, tiny sizes.
TINY = {
    "model_type": "sarvam_mla", "hidden_act": "silu",
    "hidden_size": 64, "num_hidden_layers": 3, "vocab_size": 512,
    "intermediate_size": 96, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "q_head_dim": 24,
    "head_dim": 32, "use_qk_norm": True, "rope_theta": 10000,
    "rope_scaling": {"type": "deepseek_yarn", "factor": 40,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "max_position_embeddings": 256,
    "num_experts": 2, "router_outputs": 8, "first_routed_expert": 0,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5,
    "moe_router_enable_expert_bias": True, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-6, "torch_dtype": "float32",
}
PRESET = "hybrid_rotary_latent_test"
CFG = ModelConfig(
    name=PRESET, tokenizer="byte", vocab_size=512, hidden_size=64,
    num_layers=6, num_heads=4, num_kv_heads=4, ffn_size=96, max_seq_len=256,
    tie_embeddings=False, rotary=True, layer_pattern="L-" + "LE" * 2,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000.0, rope_factor=40.0, rope_original_max_pos=32,
    rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
    rope_mscale_all_dim=1.0, num_experts=8, experts_first=0,
    experts_count=2, moe_ffn_size=32, shared_ffn_size=32,
    experts_per_token=3, router_scale=2.5, expert_act="swiglu",
    norm_eps=1e-6, dtype="float32")
SEED = 5
BLOCK = 16
TABLE = jnp.arange(1, 9, dtype=jnp.int32)
# 100 ids: 72 of prompt and 28 decode steps: the latent rows cross six
# block boundaries, and every position from 32 on lies past the tiny
# preset's original_max_position_embeddings.
TOKENS = np.random.default_rng(0).integers(0, 500, 100).astype(np.int32)
N_PROMPT = 72
# Float32 against float32: what the two orders of operations leave (the
# absorbed decode form against the reference's up-projected keys; the
# reference's blocks of heads).  Read at this seed: 6e-8.
F32_TOL = 2e-5


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"rotary_latent_moe_{kind}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_reference():
    return _load("reference", "rotary_latent_moe_decoder")


@pytest.fixture(scope="module")
def ref():
    return _load_reference()


def _cfg(dtype="float32", **kw):
    return dataclasses.replace(CFG, dtype=dtype, **kw)


def _params(cfg, seed=SEED):
    return jax.jit(lambda s: models.init_params(cfg, s))(jnp.int32(seed))


def _pool(cfg, slots=2):
    return paged_kv.init_pool(cfg, paged_kv.PagedConfig(
        block_size=BLOCK, max_slots=slots, max_seq_len=128))


_CHUNK_FNS = {}


def _chunk_fn(cfg, window):
    if (cfg, window) not in _CHUNK_FNS:
        _CHUNK_FNS[cfg, window] = jax.jit(
            lambda params, pool, piece, start, total, table:
            paged_kv.chunk_prefill_paged(cfg, params, piece, start, total,
                                         pool, table, window))
    return _CHUNK_FNS[cfg, window]


def _prefill(cfg, params, pool, tok, table=TABLE, chunk=16, pad=0,
             windows=(128,)):
    """``tok`` through the chunk program, the last chunk right-padded
    with ``pad``, each chunk at the smallest of ``windows`` that holds
    its end.  Returns (the last valid position's hidden, pool)."""
    for start in range(0, len(tok), chunk):
        piece = np.full((1, chunk), pad, np.int32)
        k = min(chunk, len(tok) - start)
        piece[0, :k] = tok[start:start + k]
        window = next(w for w in windows if w >= start + chunk)
        hidden, pool = _chunk_fn(cfg, window)(
            params, pool, jnp.asarray(piece), jnp.array([start]),
            jnp.array([len(tok)]), table)
    return hidden[0, k - 1], pool


def _serve(cfg, params, tok=TOKENS, n_prompt=N_PROMPT, **kw):
    """Chunked prefill of ``tok[:n_prompt]`` then teacher-forced decode of
    the rest, the sequence in batch slot 1 beside an idle slot 0; logits
    at positions n_prompt-1 ..."""
    last, pool = _prefill(cfg, params, _pool(cfg), tok[:n_prompt], **kw)
    out = [transformer.logits_from_hidden(params, last)]
    tables = jnp.stack([jnp.zeros(8, jnp.int32), TABLE])
    step = jax.jit(lambda params, pool, cur, pos: paged_kv.decode_step_paged(
        cfg, params, cur, pos, pool, tables))
    for p in range(n_prompt, len(tok)):
        logits, pool = step(params, pool, jnp.asarray([0, tok[p]]),
                            jnp.array([0, p]))
        out.append(logits[1])
    return np.stack([np.asarray(x, np.float32) for x in out])


def _reference(ref, model=TINY, tok=TOKENS, n_prompt=N_PROMPT, seed=SEED):
    return np.asarray(ref.logits(
        model, ref.init_weights(model, seed), jnp.asarray(tok[None]),
        jnp.arange(n_prompt - 1, len(tok))[None]))[0]


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def want(ref):
    return _reference(ref)


@pytest.fixture(scope="module")
def got():
    return _serve(CFG, _params(CFG))


# (1) against the reference ------------------------------------------------------

def test_float32_chunks_then_decode_match_the_reference(ref, got, want):
    assert ref.pattern(TINY) == CFG.layer_pattern == "L-LELE"
    assert (CFG.layer_lead, CFG.layer_period) == ("L-", "LE")
    assert "head" in _params(CFG)                       # untied
    assert got.shape == want.shape == (len(TOKENS) - N_PROMPT + 1, 512)
    # Every kept position lies past the original context: YaRN's slowed
    # pairs and its magnitude are what is compared.
    assert N_PROMPT - 1 > CFG.rope_original_max_pos
    assert _rel(got, want) < F32_TOL


@pytest.mark.parametrize("chunk", [7, 24, 72])
def test_chunk_edges_anywhere_give_the_same_numbers(chunk, got, want):
    # 7 cuts the prompt at odd and even positions, inside blocks, and
    # pads its last chunk; 24 is a block and a half; 72 the prompt whole.
    other = _serve(CFG, _params(CFG), chunk=chunk)
    assert _rel(other, want) < F32_TOL
    np.testing.assert_allclose(other, got, rtol=0, atol=3e-5)


def test_chunks_on_a_window_ladder_give_the_same_numbers(got):
    # Each chunk attends the smallest rung that holds its end, as the
    # engine's lane does: masked columns beyond a chunk's end add nothing.
    other = _serve(CFG, _params(CFG), windows=(16, 32, 64, 128))
    np.testing.assert_allclose(other, got, rtol=0, atol=3e-5)


def test_bfloat16_chunks_then_decode_stay_within_its_rounding(ref):
    cfg = _cfg("bfloat16")
    out = _serve(cfg, _params(cfg))
    full = _reference(ref, {**TINY, "torch_dtype": "bfloat16"})
    # bfloat16 weights on both sides; the system also rounds activations
    # (2^-9 a rounding through 6 sublayers) and a top-3 choice made by a
    # hair may flip, the reference none.  Read at this seed: 3.3e-3.
    assert _rel(out, full) < 2e-2


def test_the_programs_frequencies_are_the_references(ref):
    # The tiny preset's ramp: pair 0 turns over 32 times in 32 positions
    # and stays plain, pairs 1-3 turn less than once and are slowed 40
    # times: a blend, neither all plain nor all slowed.
    mine = np.asarray(latent_moe.yarn_inv_freq(CFG))
    np.testing.assert_allclose(mine, np.asarray(ref.yarn_inv_freq(TINY)),
                               rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(mine, plain / [1, 40, 40, 40], rtol=1e-6)
    m = 0.1 * math.log(40) + 1
    assert latent_moe.softmax_scale(CFG) == pytest.approx(24 ** -0.5 * m * m)
    assert ref.softmax_scale(TINY) == pytest.approx(24 ** -0.5 * m * m)


# (2) each part of the mathematics, dropped from the reference -------------------

def _patch(ref, what, monkeypatch, model):
    if what == "the rotation":
        monkeypatch.setattr(ref, "rotate", lambda model, x: x)
    elif what == "YaRN's blend":
        monkeypatch.setattr(ref, "yarn_inv_freq", lambda model: float(
            model["rope_theta"]) ** (-jnp.arange(
                0, model["qk_rope_head_dim"], 2, dtype=jnp.float32)
                / model["qk_rope_head_dim"]))
    elif what == "m^2 in the scale":
        monkeypatch.setattr(ref, "softmax_scale", lambda model: (
            model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5)
    elif what == "the latent's norm":
        monkeypatch.setattr(ref, "latent_norm", lambda model, c: c)
    elif what == "the router's bias":
        monkeypatch.setattr(ref, "ROUTER_BIAS_STD", 0.0)
    elif what == "2.5":
        model["routed_scaling_factor"] = 1.0
    elif what == "the shared expert":
        model["num_shared_experts"] = 0
    else:
        raise KeyError(what)


# The least multiple of the float32 tolerance each dropped term moves the
# logits by; read at this seed: the latent's norm 140, the shared expert
# 99, 2.5 42, the router's bias 38; m^2 4.7, YaRN's blend 3.9 and the
# rotation 3.6, because at these widths a matrix of normal(0, 0.02) leaves
# the scores near 0.01 and the softmax near uniform
# (test_a_latent_layer_alone_at_scores_of_order_one holds those three to
# 1000 where the scores are of order one, as at the published widths).
# (The experts' three small matrices in a row add little beside a residual
# of size one.)
DROPPED = {"the rotation": 2, "YaRN's blend": 2, "m^2 in the scale": 2.5,
           "the latent's norm": 70, "the router's bias": 20, "2.5": 20,
           "the shared expert": 50}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_a_dropped_term_fails_the_float32_tolerance(what, got, monkeypatch):
    ref = _load_reference()
    model = dict(TINY)
    _patch(ref, what, monkeypatch, model)
    assert _rel(got, _reference(ref, model)) > DROPPED[what] * F32_TOL


def test_a_latent_layer_alone_at_scores_of_order_one(ref, monkeypatch):
    # ONE "L" layer alone with its query and cache projections 16 times
    # larger (scores of order one, as the published widths give them): a
    # chunk of 72 from position 0, then 28 steps through the latent pool,
    # against the reference's full attention; positions to 100, three
    # times the original context.
    lp = hybrid_ssm.init_layer(CFG, jax.random.PRNGKey(SEED), "L")
    lp = dict(lp, wq=16 * lp["wq"], w_kva=16 * lp["w_kva"])
    n, n_chunk = 100, 72
    x = jnp.asarray(np.random.default_rng(6).normal(size=(n, 64)),
                    jnp.float32)
    pool = {"c": jnp.zeros((2, 9, BLOCK, 128), jnp.float32)}
    pos = jnp.arange(n)
    blk, off = TABLE[pos // BLOCK], pos % BLOCK
    rope = latent_moe.rope_sincos(CFG, pos)
    out, pool = hybrid_ssm._latent(CFG, lp, x[None, :n_chunk], pool, 1, {
        "row": 0, "table": TABLE, "window": 128, "q_pos": pos[None, :n_chunk],
        "blk": blk[None, :n_chunk], "off": off[None, :n_chunk],
        "rope": tuple(a[None, :n_chunk] for a in rope)})
    outs = [out[0]]
    for p in range(n_chunk, n):
        out, pool = hybrid_ssm._latent(CFG, lp, x[None, p:p + 1], pool, 1, {
            "tables": TABLE[None, :7], "pos": pos[p:p + 1],
            "blk": blk[None, p:p + 1], "off": off[None, p:p + 1],
            "rope": tuple(a[None, p:p + 1] for a in rope)})
        outs.append(out[0])
    got = np.concatenate([np.asarray(o) for o in outs])
    assert float(jnp.abs(pool["c"][0]).max()) == 0.0       # layer 1's rows
    # The row at rest: 24 normalised latent numbers, 8 rotated ones, and
    # zeros up to the lane-width.
    assert float(jnp.abs(pool["c"][1, 1:8, :, 32:]).max()) == 0.0
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.latent_attention(TINY, lp, x))
        assert _rel(got, want) < F32_TOL
        for what in ("the rotation", "YaRN's blend", "m^2 in the scale",
                     "the latent's norm"):
            with monkeypatch.context() as patch:
                _patch(ref, what, patch, dict(TINY))
                wrong = np.asarray(ref.latent_attention(TINY, lp, x))
            assert _rel(got, wrong) > 1000 * F32_TOL, what


def test_the_terms_a_test_drops_are_drawn_away_from_their_trivial_values():
    params = _params(CFG)
    experts = params["periods"][1]
    assert np.abs(np.asarray(experts["router_bias"])).max() > 1e-3
    assert "ws_gate" in experts and CFG.router_scale == 2.5
    assert latent_moe.softmax_scale(CFG) > 1.8 * 24 ** -0.5


# (3) the shares ---------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_references_whole_layer(ref):
    whole = _cfg(experts_first=0, experts_count=8)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 64)),
                    jnp.float32)
    key = jax.random.PRNGKey(SEED)
    lp = hybrid_ssm.init_layer(whole, key, "E")
    parts, counts = [], []
    for first in (0, 2, 4, 6):
        cfg = _cfg(experts_first=first, experts_count=2)
        share = hybrid_ssm.init_layer(cfg, key, "E")
        # An expert's matrix is the same whichever share holds it.
        for name in hybrid_ssm.EXPERT_KEYS:
            np.testing.assert_array_equal(
                np.asarray(share[name]), np.asarray(lp[name][first:first + 2]))
        # The sigmoid router carries nothing: a zero-wide state.
        out, n, _ = hybrid_ssm._experts(cfg, share, x[None], None, None,
                                        jnp.zeros((1, x.shape[0], 0)))
        parts.append(np.asarray(out[0]))
        counts.append(np.asarray(n))
    shared = np.asarray(hybrid_ssm.shared_expert(lp, x))
    model = dict(TINY, num_experts=8)
    # The program stores the experts' matrices zero-padded to whole
    # lane-widths; the reference takes them at the published sizes, here
    # the program's own (its keys are the program's: test (1)).
    cut = {"we_gate": lp["we_gate"][:, :64, :32],
           "we_up": lp["we_up"][:, :64, :32],
           "we_down": lp["we_down"][:, :32, :64]}

    def held(model, w, e0, n):
        return tuple(cut[name][e0:e0 + n] for name in hybrid_ssm.EXPERT_KEYS)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts_layer(model, lp, x, make=held))
        routed = np.asarray(ref.experts_layer(
            dict(model, num_shared_experts=0), lp, x, make=held))
    # Experts 0-1, 2-3, 4-5 and 6-7, the shared expert (which every rank
    # computes alike) counted once: the whole layer.  Float32 sums in
    # another order: 1e-5 of outputs of size 1.
    np.testing.assert_allclose(sum(parts) - 3 * shared, want, atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(want - routed, shared, atol=1e-5, rtol=0)
    # Every assignment is held by exactly one share: 12 tokens x 3.
    assert sum(c[:2].sum() for c in counts) == 36
    for c in counts:
        assert c[:2].sum() + c[2] == 36


# (4) a pattern with no row kind -----------------------------------------------------

def test_the_pool_pages_latent_rows_and_its_rows_are_zero_layers_deep():
    pool = _pool(CFG)
    assert CFG.cache_row_width == 32 and CFG.kv_layers == 3
    assert CFG.cache_row_rest_width == 128
    assert list(pool) == ["c", "s", "t", "owner"]
    assert pool["c"].shape == (3, 17, BLOCK, 128)
    # No "M", "K" or "C": the arrays every program carries are there,
    # empty; a row a slot is still named.
    assert pool["s"].shape[:2] == (0, 2) and pool["s"].size == 0
    assert pool["t"].shape[:2] == (0, 2) and pool["t"].size == 0
    assert pool["owner"].shape == (2,)
    formats = paged_kv.pool_formats(pool)
    assert all(f["row_major"] for f in formats.values())
    # A chunk and a step give the empty arrays back as they were.
    params = _params(CFG)
    _, after = _prefill(CFG, params, pool, TOKENS[:20])
    assert {k: (v.shape, v.dtype) for k, v in after.items()} == {
        k: (v.shape, v.dtype) for k, v in pool.items()}
    assert np.asarray(after["owner"]).tolist() == [1, 0]


def test_padding_and_an_idle_slot_leave_no_trace():
    params = _params(CFG)
    tok = TOKENS[:40]
    a, _ = _prefill(CFG, params, _pool(CFG), tok, pad=0)
    b, _ = _prefill(CFG, params, _pool(CFG), tok, pad=77)
    # What the 8 padded positions of the last chunk hold reaches no valid
    # position.  Bit for bit.
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


TIER = dict(name="nano", model_preset=PRESET + "_f32", decode_batch=2,
            max_new_tokens=8, kv_block_size=BLOCK,
            prefill_buckets=(32, 64, 128, 256), prefill_chunk_tokens=16,
            decode_steps_per_tick=2, enable_prefix_cache=False)


@pytest.fixture(scope="module")
def engine():
    MODEL_PRESETS[PRESET + "_f32"] = _cfg(name=PRESET + "_f32")
    eng = ContinuousBatchingEngine(TierConfig(**TIER), seed=SEED)
    yield eng
    eng.stop()
    del MODEL_PRESETS[PRESET + "_f32"]


def test_tick_nests_two_whiles_and_the_chunk_program_one(engine,
                                                         monkeypatch):
    # The MODEL's loops: on this CPU the grouped product's kernel is
    # interpreted, its loop over the touched groups an HLO ``while`` of
    # its own (tests/test_hybrid_ssm.py, the same test).  The engine's
    # first use: nothing has traced its programs yet.
    from distributed_llm_tpu.ops import grouped_product
    monkeypatch.setattr(grouped_product, "serves", lambda *a: False)
    monkeypatch.setattr(grouped_product, "serves_ffn", lambda *a: False)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tick = engine._decode_step().lower(
        engine.params, engine.pool, i32(2, 2), i32(2), i32(2),
        jax.ShapeDtypeStruct((2,), jnp.float32), key).compile().as_text()
    # Steps of a tick, periods of a step — the lead "L-" inline ahead of
    # the periods' scan, which stays a loop of TWO repetitions.
    assert _while_depth(tick) == 2
    chunk = engine._chunk_prefill_fn(16, 256).lower(
        engine.params, engine.pool, i32(1, 16), i32(1), i32(1), i32(16),
        key, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    assert _while_depth(chunk) == 1
    # The traced programs keep the scopes the per-layer metrics read, and
    # the rotation's sines are made once a program, under step_inputs.
    for scope in ("latent_attention", "kv_write", "mixer_proj",
                  "moe_router", "moe_experts", "shared_expert", "ffn",
                  "head", "step_inputs"):
        assert scope in chunk and scope in tick, scope
    for text in (tick, chunk):
        sines = [line for line in text.splitlines() if " sine(" in line]
        assert sines and all("step_inputs" in line for line in sines)


def test_two_interleaved_sequences_are_served_and_every_block_freed(engine):
    prompts = {"a": "a long prompt of some fifty characters, two chunks..",
               "b": "a short one"}
    alone = {k: engine.generate(p, max_new_tokens=6).token_ids
             for k, p in prompts.items()}
    free = engine.allocator.available
    out = {}
    threads = [threading.Thread(target=lambda k=k, p=p: out.update(
        {k: engine.generate(p, max_new_tokens=6).token_ids}))
        for k, p in prompts.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Side by side in two slots, each is what it is alone.
    assert out == alone
    assert engine.allocator.available == free == engine.paged.num_blocks - 1
    state = engine.state_stats()
    assert state["mixer"] == "none" and state["layers"] == 0
    assert state["rows"] == 2 and state["rows_in_use"] == 0
    assert state["row_bytes"] == 0 and state["resets_total"] >= 4
    assert state["kv_layers"] == 3
    assert state["kv_bytes_per_token"] == 3 * 32 * 2
    assert engine.decode_attention_form() == "latent"
    assert {k: tuple(f["shape"][:2]) for k, f in
            engine.pool_stats()["formats"].items()
            if k in "st"} == {"s": (0, 2), "t": (0, 2)}


def test_engine_generates_the_references_greedy_tokens(engine, ref):
    prompt = "a rotated row a token and no row a slot"
    out = engine.generate(prompt, max_new_tokens=6)
    ids = [engine.tokenizer.bos_id] + list(prompt.encode())
    seq = np.asarray(ids + list(out.token_ids[:6]), np.int32)
    full = _reference(ref, TINY, seq, len(ids), seed=SEED)
    # Greedy: each generated id is the reference's largest logit at the
    # position before it, or within float32's noise of it.
    for i, tok in enumerate(out.token_ids[:6]):
        row = full[i]
        assert row[tok] >= row.max() - 1e-4, (i, tok, int(row.argmax()))


# (5) what the family passes and refuses ----------------------------------------------

def test_check_passes_latent_under_rotary():
    hybrid_ssm.check(CFG)
    hybrid_ssm.check(_cfg(rotary=False))             # and without
    kimi = MODEL_PRESETS["hybrid_kda_test"]
    hybrid_ssm.check(kimi)
    # "K" rows beside a rotated "L": nothing of "K" reads the term.
    hybrid_ssm.check(dataclasses.replace(kimi, rotary=True))


@pytest.mark.parametrize("what,kw,match", [
    ("* under rotary", dict(preset="hybrid_test", rotary=True),
     "rotary False"),
    ("L with q_lora_rank", dict(q_lora_rank=8), "q_lora_rank 0"),
    ("L beside *", dict(layer_pattern="L-*ELE", rotary=False), "beside no"),
    ("L beside C", dict(layer_pattern="L-CELE"), "beside no|has no"),
    ("L without a latent row", dict(kv_lora_rank=0),
     "only a pattern with 'L'"),
    ("an unknown kind", dict(layer_pattern="L-LEQE"), "characters of"),
])
def test_check_still_refuses_by_name(what, kw, match):
    kw = dict(kw)
    base = MODEL_PRESETS[kw.pop("preset")] if "preset" in kw else CFG
    with pytest.raises(ValueError, match=match):
        hybrid_ssm.check(dataclasses.replace(base, **kw))


@pytest.mark.parametrize("what,kw", [
    ("kv_quantize", dict(kv_quantize="int8")),
    ("draft_preset", dict(draft_preset="draft_test")),
    ("host_kv_bytes", dict(host_kv_bytes=1 << 20)),
    ("enable_prefix_cache", dict(enable_prefix_cache=True)),
    ("prefill_chunk_tokens", dict(prefill_chunk_tokens=0)),
])
def test_unsupported_combinations_raise_by_the_familys_one_row(engine, what,
                                                               kw):
    # The pattern added no row of its own to the family's refusals.
    with pytest.raises(ValueError, match="state-space hybrid family"):
        ContinuousBatchingEngine(TierConfig(**{**TIER, **kw}), seed=0)


# (6) the pattern without rotary is what it was ----------------------------------------

def _latent_before(cfg, lp, h_in, pool, li, ctx):
    """``hybrid_ssm._latent`` as it stood before ``L`` could rotate (commit
    fd33b85), verbatim: ``sin`` None, whatever the configuration."""
    chunk = "row" in ctx
    if chunk:
        bs = pool["c"].shape[2]
        tables, q_pos = ctx["table"][None, :ctx["window"] // bs], ctx["q_pos"]
    else:
        tables, q_pos = ctx["tables"], ctx["pos"][:, None]
    out, rows = latent_moe._attend(cfg, lp, h_in, None, None, q_pos,
                                   pool["c"], li, ctx["blk"], ctx["off"],
                                   tables, absorbed=not chunk)
    return hybrid_ssm.quant.matmul(out, lp["wo"]), {**pool, "c": rows}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kimis_pattern_computes_what_it_did_bit_for_bit(dtype, monkeypatch):
    import test_hybrid_kda as kda
    cfg = kda._cfg(dtype)
    assert not cfg.rotary and cfg.layers_of("L") == 2
    params = kda._params(cfg)
    now = kda._serve(cfg, params)
    # No sine in the program: nothing of the rotary is traced.
    text = jax.jit(lambda p, pool: paged_kv.decode_step_paged(
        cfg, p, jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32), pool,
        jnp.zeros((2, 8), jnp.int32))).lower(params, kda._pool(cfg)).as_text()
    assert "sine" not in text and "cosine" not in text
    monkeypatch.setattr(hybrid_ssm, "_latent", _latent_before)
    kda._CHUNK_FNS.clear()
    before = kda._serve(cfg, params)
    kda._CHUNK_FNS.clear()
    np.testing.assert_array_equal(now, before)


# the route and /stats ------------------------------------------------------------------

@pytest.fixture(scope="module")
def asked():
    """A tiny cluster whose nano tier serves the pattern, warmed, one
    chat through it, and what ``GET /debug/programs`` and ``GET /stats``
    say."""
    from distributed_llm_tpu.config import tiny_batched_cluster
    from distributed_llm_tpu.obs import Observability
    from distributed_llm_tpu.serving.app import create_app
    from distributed_llm_tpu.serving.router import Router
    MODEL_PRESETS[PRESET] = CFG
    base = tiny_batched_cluster()
    cluster = dataclasses.replace(base, nano=dataclasses.replace(
        base.nano, model_preset=PRESET, decode_batch=4, kv_block_size=16,
        prefill_buckets=(16, 32, 64, 128), prefill_chunk_tokens=16,
        enable_prefix_cache=False))
    router = Router(cluster=cluster,
                    observability=Observability(slow_ms=None))
    try:
        client = create_app(router=router).test_client()
        engine = router.tiers["nano"].server_manager.engine()
        engine.generate("x" * 40, max_new_tokens=4)
        yield {"doc": client.get("/debug/programs").get_json(),
               "stats": client.get("/stats").get_json(),
               "compiled": {stage: sorted(engine._compiled.get(stage, ()))
                            for stage in ("decode", "chunk_prefill")}}
    finally:
        router.drain()
        del MODEL_PRESETS[PRESET]


SCOPES = {"latent_attention", "kv_write", "mixer_proj", "moe_router",
          "moe_experts", "shared_expert", "ffn", "head", "step_inputs"}


def test_the_route_serves_the_patterns_programs_by_window_rung(asked):
    entries = asked["doc"]["tiers"]["nano"]
    ticks = [e for e in entries if e["stage"] == "decode"]
    chunks = [e for e in entries if e["stage"] == "chunk_prefill"]
    assert [e["window_tokens"] // 16 for e in ticks] == [
        key[0] for key in asked["compiled"]["decode"]]
    assert [(e["chunk_tokens"], e["window_tokens"]) for e in chunks] == [
        tuple(key) for key in asked["compiled"]["chunk_prefill"]]
    assert len(ticks) >= 2 and len(chunks) >= 1
    for e in entries:
        scopes = {v["scope"] for v in e["ops"].values()}
        assert SCOPES | {"layer_scan", "sample"} <= scopes, (
            e["stage"], sorted(SCOPES - scopes))
        # No row kind: no scope of a recurrence in either program.
        assert not any(s and s.startswith(("ssm_", "kda_", "cca_"))
                       for s in scopes)
        unscoped = [k for k, v in e["ops"].items() if v["scope"] is None]
        assert len(unscoped) < 0.15 * len(e["ops"]), unscoped
        assert e["pool_sized_moves"] == {}
        if e["stage"] == "decode":
            assert e["attention_form"] == "latent"


def test_stats_keep_the_held_and_absent_counts_and_chunks_by_window(asked):
    tier = asked["stats"]["tiers"]["nano"]
    moe = tier["moe"]
    for stage in ("prefill", "decode"):
        held = np.asarray(moe["expert_tokens"][stage])
        assert held.shape == (2, 2)                # 2 "E" layers x 2 held
        # A quarter of the router is held: some assignments went away.
        assert moe["absent_assignments"][stage] > 0
        assert held.sum() + moe["absent_assignments"][stage] \
            == 3 * 2 * moe["steps"][stage] * (16 if stage == "prefill"
                                              else 4)
    by_window = tier["prefill"]["chunks_by_window"]
    assert sum(by_window.values()) >= 3 and tier["state"]["mixer"] == "none"
    assert tier["pool"]["formats"]["c"]["shape"][-1] == 128


# configuration, roofline, int8 ---------------------------------------------------------

def test_roofline_counts_the_patterns_matrices_and_latent_rows():
    from distributed_llm_tpu.utils import roofline
    cfg = _cfg("bfloat16")
    h = 64
    latent = h * 4 * 24 + h * 32 + 24 * 4 * 32 + 4 * 16 * h
    fixed = h * 8 + 3 * h * 32                  # router, gated shared
    expert, mlp = 3 * h * 32, 3 * h * 96
    # Of a token's 3 choices the held quarter computes 0.75 at uniform
    # routing.
    assert roofline.active_matmul_params(cfg) == int(
        3 * latent + 2 * (fixed + 0.75 * expert) + mlp) + 512 * h
    # Every held expert, embedding and head, a gain a sublayer and the
    # final one.
    assert roofline.weight_bytes(cfg) == (
        3 * latent + 2 * (fixed + 2 * expert) + mlp + 2 * 512 * h
        + 7 * h) * 2
    assert roofline.kv_bytes_per_pos(cfg) == 3 * 32 * 2
    assert roofline.state_row_bytes(cfg) == 0
    # Attention over positions: a head's scores over nope + rope and its
    # values, 4 heads x (24 + 16) / 2, in the three latent layers.
    assert roofline._attention_width_layers(cfg) == (80, 3)
    work = roofline.prefill_work(cfg, 32, 16)
    assert work["flops"] == 2.0 * roofline.active_matmul_params(cfg) * 16 \
        + 2.0 * 80 * 3 * (32 ** 2 - 16 ** 2)


def test_int8_weights_reach_the_patterns_matrices():
    from distributed_llm_tpu.ops.quant import is_quantized, quantize_params
    q = jax.jit(quantize_params)(_params(CFG))
    assert is_quantized(q["embed"]) and is_quantized(q["head"])
    lead_latent, lead_mlp = q["lead"]
    latent, exp = q["periods"]
    for lp in (lead_latent, latent):
        assert all(is_quantized(lp[k])
                   for k in ("wq", "w_kva", "w_kvb", "wo"))
        assert not is_quantized(lp["kv_ln"])
    assert all(is_quantized(lead_mlp[k])
               for k in ("w_gate", "w_up", "w_down"))
    assert all(is_quantized(exp[k]) for k in hybrid_ssm.EXPERT_KEYS
               + ("ws_gate", "ws_up", "ws_down"))
    assert not is_quantized(exp["router"])
    # And the quantized tree serves: a layer at a time, XLA's product.
    last, _ = _prefill(CFG, q, _pool(CFG), TOKENS[:20])
    assert np.isfinite(np.asarray(last)).all()


def test_the_benchmarks_family_maps_the_published_keys_to_this_pattern(
        monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    family = _load("families", "rotary_latent_moe_decoder")
    cfg = family.model_config(PRESET, TINY)
    assert cfg == CFG
    assert family.pattern(PRESET, TINY) == "L-LELE"
    assert family.chunk_loops(TINY) == 1
    for key, bad in (("model_type", "deepseek_v3"), ("use_qk_norm", False),
                     ("q_head_dim", 32), ("head_dim", 64),
                     ("rope_scaling", dict(TINY["rope_scaling"],
                                           type="linear"))):
        with pytest.raises(ValueError, match=key):
            family.model_config(PRESET, dict(TINY, **{key: bad}))
    with pytest.raises(ValueError, match="not among the router's"):
        family.model_config(PRESET, dict(TINY, first_routed_expert=7))
