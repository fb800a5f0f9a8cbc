"""CPU rehearsal of chip_smoke.py's phase functions.

The script itself only succeeds on a TPU (its device phase has no CPU
branch).  Its phase functions take the cluster and the devices as
arguments, so the control flow the chip run depends on — the request
mix, the /stats and /metrics checks, the placement and no-recompile
checks, the kernel-vs-reference comparison, the four-chip carve — is
driven here on the virtual CPU devices with the tiny tiers and Pallas in
interpret mode.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (repo-root script)

from distributed_llm_tpu.config import tiny_batched_cluster  # noqa: E402


def test_device_phase_refuses_anything_but_a_tpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.phase_device(1)


def test_script_exits_nonzero_off_tpu_and_prints_no_result():
    """As the driver first runs it: in a sandbox without the chip the
    script fails before serving anything, and its stdout carries no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "[smoke:serve]" not in proc.stdout
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), line


def test_serve_and_what_ran_on_the_tiny_cluster(capsys, monkeypatch):
    """The one-chip layout: both tiers on ONE device, the full request
    mix, then the record of what ran, then a clean drain."""
    from distributed_llm_tpu.obs import program_scopes
    served = chip_smoke.phase_serve(tiny_batched_cluster(),
                                    devices=jax.devices()[:1])
    try:
        what = chip_smoke.phase_what_ran(served)
        pool = chip_smoke.phase_pool_programs(served)
        # A pool-sized array that rests in another order than row-major
        # fails the phase before anything is compiled (ISSUE 57).
        engine = chip_smoke._engine(served.router, "nano")
        rest = engine.pool_stats()
        rest["formats"]["k"] = dict(rest["formats"]["k"], row_major=False,
                                    major_to_minor=[0, 2, 3, 1])
        monkeypatch.setattr(engine, "pool_stats", lambda: rest)
        monkeypatch.setattr(program_scopes, "POOL_SIZED_BYTES", 0)
        with pytest.raises(chip_smoke.SmokeFailure, match="not row-major"):
            chip_smoke.phase_pool_programs(served, ["nano"])
    finally:
        chip_smoke.phase_drain(served)

    labels = [r["label"] for r in served.record["requests"]]
    assert labels == ["nano#1", "nano#2", "orin#1", "orin#2", "orin#3-long",
                      "nano#1-repeat", "nano#1-followup", "nano#stream"]
    by = {r["label"]: r for r in served.record["requests"]}
    assert by["nano#1-repeat"]["cache_hit"] is True
    assert by["orin#3-long"]["device"] == "orin"
    assert all(r["gen_tokens"] > 0 for r in served.record["requests"]
               if "gen_tokens" in r)
    # The pool phase compiled each tier's tick, one chunk program and
    # copy_block, and found the pool's format the same in and out.
    for tier in ("nano", "orin"):
        programs = {k: v for k, v in pool[tier].items() if k != "at_rest"}
        assert len(programs) == 3 and set(pool[tier]["at_rest"]) == {"k", "v"}
        assert all(f["row_major"] for f in pool[tier]["at_rest"].values())
        assert all(f["formats_match"] and f["pool_sized_moves"] == {}
                   for f in programs.values()), programs
    # Warm-up is reported apart from the requests, per tier.
    assert set(served.record["warmup_s"]) == {"nano", "orin"}
    # Off-TPU the batched engine takes the fused ragged tick.
    for tier in ("nano", "orin"):
        row = what[tier]
        assert row["engine"] == "ContinuousBatchingEngine"
        assert row["tick"] == "ragged fused"
        # ... over the whole token-major pool: rows attended merged.
        assert row["decode_attention"] == "merged"
        # The tiny tiers' replies are a few ticks each; the share of them
        # that took everything from the tick before is a real number.
        assert 0.0 < row["tick_resident_share"] <= 1.0
        assert row["compiled_after_requests"]["decode"] == 1
    out = capsys.readouterr().out
    assert "[smoke:what-ran] pallas kernels: interpret" in out
    assert served.router.draining is True


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_comparison_at_tiny_widths(dtype):
    """The comparison code the chip runs at nano_1b widths, here in
    interpret mode at a geometry the interpreter finishes quickly."""
    cases = chip_smoke.kernel_cases(
        8, 4, 16, dtype, batch=3, block=16, blocks_per_slot=4,
        prefill_len=64, grouped={"tiny": (24, 6, 256, 128)},
        scan=(16, 8, 256), latent=(2, 16, 512, 400, 128, 256))
    assert {c.kind for c in cases.values()} == {
        "prefill", "paged_decode", "grouped_product", "ssm_scan",
        "latent_chunk"}
    errs = chip_smoke.compare_kernels(cases, jnp.dtype(dtype).name)
    assert set(errs) == set(cases)


def test_kernel_comparison_catches_a_wrong_kernel():
    cases = chip_smoke.kernel_cases(
        8, 4, 16, jnp.float32, batch=3, block=16, blocks_per_slot=4,
        prefill_len=64)
    good = cases["paged_rows_decode_attention"]

    def off_by_one_block(q, kp, vp, tables, pos):
        return good.pallas(q, kp, vp, tables, jnp.maximum(pos - 16, 0))

    with pytest.raises(chip_smoke.SmokeFailure, match="differs"):
        chip_smoke.compare_kernels(
            {"paged_rows_decode_attention":
             good._replace(pallas=off_by_one_block)}, "float32")


def test_kernel_phase_refuses_interpret_mode():
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
        chip_smoke.phase_kernels()


def test_four_chip_placement_rehearsal_on_virtual_devices():
    """--chips 4 (a) on four virtual devices: nano alone on device 0,
    orin shrunk to tp=2 on devices 1-2, weights at total/tp each."""
    devices = jax.devices()[:4]
    chip_smoke.phase_placement(tiny_batched_cluster(), devices)


def test_four_chip_tp_parity_rehearsal_on_virtual_devices():
    """--chips 4 (b): the same preset at tp=1 and tp=2 agrees layer by
    layer through the engines' own prefill program — and here, on the
    CPU's virtual devices, token for token."""
    tier = dataclasses.replace(
        tiny_batched_cluster().orin, tp=2, max_new_tokens=12,
        enable_prefix_cache=False)
    out = chip_smoke.phase_tp_parity(
        tier, jax.devices()[1:3],
        ["short question about rivers please",
         "what is the tallest mountain in asia today"])
    assert out["tokens_tp1"] == out["tokens_tp2"]
    assert max(out["kv_rel_err"]) <= chip_smoke.TP_KV_REL_TOL


def test_tp_parity_catches_a_wrongly_sharded_engine(monkeypatch):
    """The comparison must fail on a wiring fault: here the tp=2 engine
    is built from another seed, which stands in for weights that landed
    on the wrong shard."""
    from distributed_llm_tpu.engine import manager as manager_mod
    real = manager_mod.EngineManager

    def other_seed_when_sharded(tier, mesh=None, **kw):
        return real(tier, mesh=mesh, seed=1 if mesh is not None else 0, **kw)

    monkeypatch.setattr(manager_mod, "EngineManager",
                        other_seed_when_sharded)
    tier = dataclasses.replace(
        tiny_batched_cluster().orin, tp=2, max_new_tokens=4,
        enable_prefix_cache=False)
    with pytest.raises(chip_smoke.SmokeFailure, match="K/V differ"):
        chip_smoke.phase_tp_parity(tier, jax.devices()[1:3],
                                   ["short question about rivers please"])


def test_smoke_cluster_is_the_accelerator_default_with_nano_1b(monkeypatch):
    """What the chip serves: default_cluster()'s accelerator branch with
    the nano tier at nano_1b, weights from the seed."""
    from distributed_llm_tpu.serving import router as router_mod
    monkeypatch.setattr(router_mod.jax, "default_backend", lambda: "tpu")
    cluster = chip_smoke.smoke_cluster()
    assert cluster.nano.model_preset == "nano_1b"
    assert cluster.nano.quantize == "none" and cluster.nano.decode_batch == 8
    assert cluster.nano.model().max_seq_len == 8192
    assert cluster.nano.kv_pool_blocks is None          # full residency
    assert cluster.orin.model_preset == "orin_bench"
    assert cluster.orin.quantize == "int8"
    assert cluster.nano.checkpoint_path is None
    assert cluster.orin.checkpoint_path is None
    json.dumps(dataclasses.asdict(cluster))       # plain config, no handles
