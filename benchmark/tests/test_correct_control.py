"""The control of ``correct`` at a size a CPU test can hold: the program's
own int8 weight path must read clearly worse against the float32
reference than the stated bfloat16 precision does, on three seeds, the
same seed must give the bit-identical statistic twice, and the sample has
to run continuation chunks on every rung of the chunk-window ladder.  (On the
chip at the published sizes: ``tools/sweep_correct.py``, results under
``sweeps/``.)"""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]
import cluster  # noqa: E402
import correct  # noqa: E402


def test_int8_control_reads_worse_than_bf16_and_repeats():
    import jax
    from distributed_llm_tpu import models
    from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
    with open(os.path.join(HERE, "configs", "smollm2-1.7b.json")) as f:
        config = json.load(f)
    e = cluster.tier_entries(config, rehearsal=True)["nano"]
    cfg = cluster.program_config(e)
    MODEL_PRESETS[e["preset"]] = cfg
    kw = dict(e["tier"], prefill_buckets=tuple(e["tier"]["prefill_buckets"]))
    tier = TierConfig(name="nano", model_preset=e["preset"], **kw)
    common = correct.tier_settings(tier, cfg)
    ends = [min(e, n - correct.N_DECODE) for n in correct.LENGTHS
            for e in range(256, n - correct.N_DECODE + 256, 256)]
    ladder = correct.chunk_windows(common["span"], common["block_size"])
    assert {next(w for w in ladder if w >= -(-e // 256) * 256)
            for e in ends} == set(ladder)
    assert all(n - correct.N_DECODE > 2 * 256 for n in correct.LENGTHS)
    for seed in (2147483659, 3000000019, 7):
        params = jax.jit(lambda s: models.init_params(cfg, s))(
            seed % (2 ** 31))
        seqs = correct.draw_sample(seed, e["model"]["vocab_size"])
        assert [s.tolist() for s in seqs] == [
            s.tolist() for s in correct.draw_sample(
                seed, e["model"]["vocab_size"])]
        want = correct.reference_logits(e["family"], e["model"], seed, seqs)
        got = correct.system_logits(cfg, params, seqs, **common)
        again = correct.system_logits(cfg, params, seqs, **common)
        ctl = correct.system_logits(
            cfg, correct.int8_weights(params, tier, cfg), seqs, **common)
        stated = correct.rel_frobenius(got, want)
        assert (got == again).all()
        assert stated < 6e-3                   # bf16 through 2 tiny layers
        assert correct.rel_frobenius(ctl, want) > 2.0 * stated


def test_storage_under_16_bits_is_found_in_each_int8_control():
    import jax
    from distributed_llm_tpu import models
    from distributed_llm_tpu.config import MODEL_PRESETS, TierConfig
    from distributed_llm_tpu.engine.paged_kv import PagedConfig, init_pool
    with open(os.path.join(HERE, "configs", "smollm2-1.7b.json")) as f:
        config = json.load(f)
    e = cluster.tier_entries(config, rehearsal=True)["nano"]
    cfg = cluster.program_config(e)
    MODEL_PRESETS[e["preset"]] = cfg
    kw = dict(e["tier"], prefill_buckets=tuple(e["tier"]["prefill_buckets"]))
    tier = TierConfig(name="nano", model_preset=e["preset"], **kw)
    params = models.init_params(cfg, 3)
    pcfg = PagedConfig(block_size=64, max_slots=1, max_seq_len=64,
                       pool_blocks=1)
    pool16, pool8 = (init_pool(cfg, pcfg, q) for q in ("none", "int8"))
    assert correct.narrow_leaves({"params": params, "pool": pool16}) == []
    assert correct.narrow_leaves({"params": params, "pool": pool8})
    assert correct.narrow_leaves(
        {"params": correct.int8_weights(params, tier, cfg), "pool": pool16})
    assert correct.narrow_leaves({"mask": jax.numpy.ones(3, bool)}) == []
