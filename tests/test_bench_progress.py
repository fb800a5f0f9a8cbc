"""Wedge-resilient bench progress/partials (VERDICT r1 #1 hardening).

A device call can hang mid-run; bench.py checkpoints every finished
section to BENCH_partial.json and a watchdog emits the partial as the
headline JSON line when device progress stalls.  These tests pin that
machinery without any device work.
"""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402  (repo-root module)


def test_sections_checkpoint_atomically(tmp_path):
    path = tmp_path / "partial.json"
    p = bench.Progress(str(path))
    p.section("backend", "tpu")
    p.section("per_strategy", {"token": {"req_per_s": 1.0}})
    data = json.loads(path.read_text())
    assert data == {"backend": "tpu",
                    "per_strategy": {"token": {"req_per_s": 1.0}}}
    # Overwrites keep the latest value.
    p.section("backend", "cpu")
    assert json.loads(path.read_text())["backend"] == "cpu"


def test_beat_resets_idle_clock(tmp_path):
    p = bench.Progress(str(tmp_path / "x.json"))
    time.sleep(0.05)
    assert p.idle_s() >= 0.05
    p.beat()
    assert p.idle_s() < 0.05


def test_watchdog_leaves_live_run_alone(tmp_path):
    p = bench.Progress(str(tmp_path / "x.json"))
    t = bench.start_watchdog(p, timeout_s=3600.0)
    assert t.daemon                      # must not block interpreter exit
    time.sleep(0.2)
    p.done.set()
    # Run completed; if the watchdog had fired it would have os._exit'd.
    assert True


def test_compact_final_line_fits_driver_tail():
    """BENCH_r02.json was an unparseable fragment: the final printed line
    outgrew the driver's ~2 KB tail capture.  compact() must keep the
    last line small while preserving the headline contract and the
    roofline verdicts."""
    result = {
        "metric": "req_per_s_general_knowledge_all_strategies",
        "value": 37.99, "unit": "req/s", "vs_baseline": 3477.0,
        "p50_ttft_ms": 11.2, "p50_latency_ms": 25.0,
        "routing_accuracy": 0.817, "decode_tok_per_s": 700.1,
        "backend": "tpu", "queries": 60,
        "utilization": {"prefill": {"mfu": 0.41, "tflops_per_s": 80.0},
                        "decode": {"hbm_util": 0.62, "hbm_gb_per_s": 500.0}},
        "per_strategy": {
            s: {"req_per_s": 9.0, "p50_ttft_ms": 11.0,
                "routing_accuracy": 0.83}
            for s in ("token", "semantic", "heuristic", "hybrid", "perf")},
        "continuous_batching": {"batching_speedup": 2.9,
                                "kv_int8": {"speedup_vs_bf16_kv": 1.24}},
        "speculative": {"speedup": 1.4, "acceptance_rate": 0.8},
        "quant": {"nano": {"speedup": 1.6}, "orin": {"speedup": 1.7}},
        "long_context": {"prefix_reuse_speedup": 8.2},
        # Bulky blocks that must NOT survive into the final line:
        "tiers": {"nano": {"phases": ["x" * 50] * 40}},
        "flagship": {"nano_1b": {"decode_tok_per_s": 51.0,
                                 "hbm_util": 0.7, "params_gb": 2.1}},
    }
    line = json.dumps(bench.compact(result))
    assert len(line) < 1600, len(line)
    data = json.loads(line)
    assert data["value"] == 37.99 and data["unit"] == "req/s"
    assert data["mfu_prefill"] == 0.41
    assert data["hbm_util_decode"] == 0.62
    assert data["verdicts"]["spec_speedup"] == 1.4
    assert data["verdicts"]["quant_speedup"]["orin"] == 1.7
    assert data["verdicts"]["flagship_decode_tok_per_s"]["nano_1b"] == 51.0
    assert "tiers" not in data


def test_watchdog_emits_partial_on_stall(tmp_path):
    """The stall path os._exit(3)s after printing the partial headline —
    exercised in a subprocess."""
    import subprocess
    code = f"""
import sys, time
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import bench
p = bench.Progress({str(tmp_path / 'p.json')!r})
p.section("backend", "tpu")
p.section("value", 9.9)
p._beat -= 100                       # simulate 100s without device progress
bench.start_watchdog(p, timeout_s=1.0)
time.sleep(30)                       # watchdog must fire long before this
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=25)
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-500:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["backend"] == "tpu" and line["value"] == 9.9
    assert "aborted" in line and "wedged" in line["aborted"]


@pytest.mark.parametrize("result,expected", [
    # A leg that caught its own exception: found, with its path.
    ({"value": 1.0, "spec_phase": {"tok_ratio": 2.0,
                                   "error": "outputs diverged"}},
     [("spec_phase", "outputs diverged")]),
    # Nested sub-checks and list rows are walked too.
    ({"spill": {"race": {"error": "boom"}},
      "legs": [{"ok": True}, {"error": "second leg"}]},
     [("spill.race", "boom"), ("legs[1]", "second leg")]),
    # The compact line's "err" digests, empty strings and non-strings
    # are not phase errors.
    ({"noisy": {"err": "x"}, "a": {"error": ""}, "b": {"error": None},
      "errors": 3}, []),
    ({}, []),
], ids=["top-level-leg", "nested-and-list", "not-errors", "empty"])
def test_phase_errors_finds_every_recorded_error(result, expected):
    """bench.py's __main__ exits non-zero when any phase recorded an
    error; this is the walk that decides."""
    assert bench.phase_errors(result) == expected


def test_main_has_no_probe_or_cpu_fallback():
    """The bench runs in one process on whatever jax finds: no child
    that takes the chip first, no retry schedule, no fallback to the
    host CPU."""
    src = open(bench.__file__, encoding="utf-8").read()
    main = src[src.index('if __name__ == "__main__":'):]
    assert "subprocess" not in main and "Popen" not in main
    assert 'jax.config.update("jax_platforms"' not in src
    assert "sys.exit(1 if failed else 0)" in main
