"""Flagship (north-star) tiers fit their submeshes and are live config.

nano_1b / orin_8b / moe_8x1b were once dead presets: nothing verified
the ~7B orin_8b (14 GB bf16) plus KV pool fits its tp=4 submesh at
16 GB/chip.  These tests budget the real init/quantize/cache/sharding
code paths via jax.eval_shape (utils/hbm_budget.py) on the CPU mesh — no
weights materialize — for the tiers config.flagship_cluster builds at
each device count it branches on.
"""

import dataclasses

import pytest

from distributed_llm_tpu.config import TierConfig, flagship_cluster
from distributed_llm_tpu.utils.hbm_budget import tier_hbm_budget


def test_nano_1b_fits_a_single_chip():
    tier = flagship_cluster(n_devices=1).nano
    b = tier_hbm_budget(tier)
    # ~1.2B params × 2B ≈ 2.4 GB + KV + parked prefix caches — ample room.
    assert 1.5 <= b["params_gb_per_chip"] <= 4.0, b
    assert b["fits"], b


def test_orin_8b_bf16_fits_its_tp4_submesh():
    tier = flagship_cluster(n_devices=8).orin
    assert tier.tp == 4 and tier.quantize == "none"
    b = tier_hbm_budget(tier)
    # ~14 GB bf16 sharded 4 ways ≈ 3.6 GB/chip (embed/norms replicated).
    assert 3.0 <= b["params_gb_per_chip"] <= 6.0, b
    assert b["fits"], b


def test_orin_8b_bf16_does_not_fit_one_chip():
    """The budget must be able to say NO: unsharded bf16 orin_8b is ~14 GB
    of weights alone — over a 16 GB chip once KV joins."""
    tier = dataclasses.replace(flagship_cluster(n_devices=8).orin, tp=1)
    b = tier_hbm_budget(tier)
    assert b["params_gb_per_chip"] >= 13.0, b
    assert not b["fits"], b


def test_orin_8b_int8_fits_the_single_bench_chip():
    """The single-chip mode: int8 weights (~7 GB) + bf16 KV + two
    parked prefix caches fit 16 GB.  KV stays bf16 by DEFAULT: int8
    weights are a fit requirement, int8 KV is a perf knob no chip
    measurement justifies, so it is opt-in via DLLM_FLAGSHIP_KV_INT8=1."""
    tier = flagship_cluster(n_devices=1).orin
    assert tier.quantize == "int8"
    assert tier.kv_quantize == "none"
    b = tier_hbm_budget(tier)
    assert 6.0 <= b["params_gb_per_chip"] <= 9.0, b
    assert b["fits"], b


def test_flagship_kv_int8_opt_in(monkeypatch):
    """The A/B flag still arms int8 KV (halving decode's KV read traffic
    for a measured re-run) — off-by-default must not mean gone."""
    monkeypatch.setenv("DLLM_FLAGSHIP_KV_INT8", "1")
    tier = flagship_cluster(n_devices=1).orin
    assert tier.kv_quantize == "int8"
    assert tier_hbm_budget(tier)["fits"]


def test_moe_8x1b_fits_a_tp4_submesh():
    """The MoE flagship: expert FFNs are sharded over the tier's tensor
    axis (parallel/sharding.py param_specs), so the ~7.5B total spreads."""
    tier = TierConfig(name="moe", model_preset="moe_8x1b", tp=4,
                      max_new_tokens=64)
    b = tier_hbm_budget(tier)
    assert b["fits"], b


def test_budget_tracks_param_count():
    """eval_shape bytes must agree with the analytic param count."""
    tier = flagship_cluster(n_devices=1).nano
    cfg = tier.model()
    b = tier_hbm_budget(tier)
    expected_gb = cfg.param_count() * 2 / 1e9
    assert abs(b["params_gb_per_chip"] - expected_gb) / expected_gb < 0.05, (
        b, expected_gb)


@pytest.mark.parametrize("n_devices", [1, 4, 8])
def test_flagship_tiers_are_budgetable_at_each_device_count(n_devices):
    """One chip, the benchmark's largest cell (4) and the north star's
    v5e-8: whichever orin ``flagship_cluster`` picks (int8 on one chip's
    worth of HBM below five devices, bf16 over tp=4 from five up), both
    tiers' budgets come back whole and say they fit."""
    cluster = flagship_cluster(n_devices=n_devices)
    assert cluster.orin.tp == (4 if n_devices >= 5 else 1)
    assert cluster.orin.quantize == ("none" if n_devices >= 5 else "int8")
    for tier in (cluster.nano, cluster.orin):
        entry = tier_hbm_budget(tier)
        assert {"params_gb_per_chip", "kv_gb_per_chip", "fits"} <= set(entry)
        assert entry["chips"] == tier.tp and entry["fits"], entry
