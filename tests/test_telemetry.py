"""Telemetry tests: window integration, log format, sampler lifecycle."""

import datetime

from distributed_llm_tpu.utils import telemetry


def _dt(ts: float) -> datetime.datetime:
    return datetime.datetime.fromtimestamp(ts)


def test_energy_integrates_constant_trace():
    t = telemetry.TierTelemetry(["nano"])
    t.samples["nano"] = [(100.0, 50.0), (101.0, 50.0), (102.0, 50.0)]
    # Constant 50 over a 2 s window → 100 unit·s.
    assert abs(t.energy_for_window("nano", _dt(100.0), _dt(102.0)) - 100.0) < 1e-9


def test_energy_subsecond_window_between_samples():
    t = telemetry.TierTelemetry(["nano"])
    t.samples["nano"] = [(100.0, 40.0), (101.0, 60.0)]
    # Window [100.25, 100.75] sits inside one sampling interval; interpolated
    # values are 45 and 55 → mean 50 over 0.5 s = 25.
    e = t.energy_for_window("nano", _dt(100.25), _dt(100.75))
    assert abs(e - 25.0) < 1e-9


def test_energy_clamps_outside_trace_and_handles_empty():
    t = telemetry.TierTelemetry(["nano"])
    assert t.energy_for_window("nano", _dt(0), _dt(1)) == 0.0
    t.samples["nano"] = [(100.0, 10.0)]
    # Single sample: clamped constant over the window.
    assert abs(t.energy_for_window("nano", _dt(99.0), _dt(101.0)) - 20.0) < 1e-9
    # Inverted window.
    assert t.energy_for_window("nano", _dt(101.0), _dt(99.0)) == 0.0


def test_sampler_lifecycle_and_log_format(tmp_path):
    t = telemetry.TierTelemetry(["nano", "orin"], interval_s=0.05)
    t.start()
    t.start()            # idempotent
    import time
    time.sleep(0.2)
    t.stop()
    assert len(t.samples["nano"]) >= 2
    path = tmp_path / "nano_power.log"
    t.save_log("nano", str(path))
    lines = path.read_text().strip().splitlines()
    assert lines and all(": " in ln for ln in lines)
    float(lines[0].split(": ")[0])   # reference-parseable "<ts>: <value>"


def test_device_memory_snapshot_shape():
    snap = telemetry.device_memory_snapshot()
    assert len(snap) == 8            # virtual CPU mesh from conftest
    assert {"device", "platform", "bytes_in_use"} <= set(snap[0])


def test_enable_persistent_compile_cache_placement(tmp_path, monkeypatch):
    """An externally set JAX_COMPILATION_CACHE_DIR is used as is (and no
    other directory is set); unset, the cache goes to ONE fixed directory
    inside the checkout — never /tmp, never a per-process name."""
    import os

    import jax

    from distributed_llm_tpu.utils import compile_cache

    prior = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        got = compile_cache.enable_persistent_compile_cache()
        assert got == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == got
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == got

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_persistent_compile_cache()
        assert got == os.path.join(repo, ".jax_cache")
        assert got == compile_cache.enable_persistent_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
        # The helper places the cache; it does not rewrite the caller's
        # environment.
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    finally:
        # Restore the suite-wide cache dir (conftest set it): this config
        # is process-global and later tests should keep their warm cache.
        jax.config.update("jax_compilation_cache_dir", prior)
