"""Health monitor tests: probing semantics (stopped ≠ failed), auto-restart,
and the remote-only perf merge."""

import numpy as np
import jax
import pytest

from distributed_llm_tpu.config import tiny_cluster
from distributed_llm_tpu.serving.health import HealthMonitor
from distributed_llm_tpu.serving.router import Router


@pytest.fixture(scope="module")
def router():
    return Router(strategy="perf", benchmark_mode=True,
                  cluster=tiny_cluster())


def test_probe_reports_tier_state(router):
    router.nano.server_manager.start_server()
    router.orin.server_manager.stop_server()
    mon = HealthMonitor(router, auto_restart=False)
    snap = mon.probe_once()
    assert snap["nano"]["state"] == "running" and snap["nano"]["ok"]
    # A stopped tier is reported but NOT a failure (lazy tiers and the
    # bench harness's stop-between-configs must not be resurrected).
    assert snap["orin"]["state"] == "stopped"
    assert snap["orin"]["consecutive_failures"] == 0


def test_stopped_tier_never_restarted(router):
    mgr = router.orin.server_manager
    mgr.stop_server()
    mon = HealthMonitor(router, max_consecutive_failures=1)
    for _ in range(3):
        mon.probe_once()
    assert not mgr.is_server_running()
    assert mon.snapshot()["orin"]["restarts"] == 0


def test_auto_restart_after_running_tier_fails(router):
    mon = HealthMonitor(router, max_consecutive_failures=2)
    mgr = router.nano.server_manager
    mgr.start_server()
    mon.probe_once()                       # marks nano as seen-running
    real_health = mgr.health
    mgr.health = lambda: {"ok": False, "tier": "nano"}   # crash-shaped
    try:
        mon.probe_once()                   # failure 1
        assert mon.snapshot()["nano"]["consecutive_failures"] == 1
        mon.probe_once()                   # failure 2 -> restart fires
    finally:
        mgr.health = real_health
    assert mon.snapshot()["nano"]["restarts"] == 1
    assert mgr.is_server_running()


def test_exchange_merges_remote_rows_only(router):
    devs = np.array(jax.devices()[:2])
    mesh = jax.sharding.Mesh(devs, ("hosts",))
    mon = HealthMonitor(router, mesh=mesh)

    perf = router.query_router.router      # PerfStrategy instance
    perf.samples["nano"].clear()
    perf.samples["orin"].clear()
    perf.update("nano", 100.0, 10, ok=True)
    before = len(perf.samples["nano"])

    # Single-process mesh: every row is ours -> exchange merges NOTHING
    # (no self-echo feedback loop).
    gathered = mon.exchange_health()
    assert gathered is not None and gathered["nano"].shape[0] == 2
    assert len(perf.samples["nano"]) == before

    # Simulated remote row (as on a real pod) DOES merge.
    remote_row = np.array([[500.0, 50.0, 4.0, 8.0]], np.float32)
    rows = np.vstack([gathered["nano"][:1], remote_row])
    HealthMonitor._merge_gathered(perf, "nano", rows,
                                  remote_mask=[False, True])
    assert len(perf.samples["nano"]) == before + 5   # capped at 5 synthetic
    merged = list(perf.samples["nano"])[-5:]
    assert all(lat == pytest.approx(500.0 / 8) for lat, _, _ in merged)
    # ok ratio 4/8 -> round(0.5 * 5) ≈ 2-3 of 5 synthetic oks
    assert 2 <= sum(ok for _, _, ok in merged) <= 3


def test_failure_heavy_remote_row_keeps_failures(router):
    perf = router.query_router.router
    perf.samples["orin"].clear()
    # 30 remote samples, only 6 ok (80% failure) — must NOT reconstitute
    # as all-healthy.
    row = np.array([[30000.0, 300.0, 6.0, 30.0]], np.float32)
    HealthMonitor._merge_gathered(perf, "orin", row, remote_mask=[True])
    merged = list(perf.samples["orin"])
    assert len(merged) == 5
    assert sum(ok for _, _, ok in merged) == 1      # round(0.2*5)


def test_exchange_noop_without_mesh_or_perf(router):
    assert HealthMonitor(router, mesh=None).exchange_health() is None
    hybrid = Router(strategy="hybrid", benchmark_mode=True,
                    cluster=tiny_cluster())
    devs = np.array(jax.devices()[:2])
    mesh = jax.sharding.Mesh(devs, ("hosts",))
    assert HealthMonitor(hybrid, mesh=mesh).exchange_health() is None


def test_monitor_lifecycle(router):
    mon = HealthMonitor(router, interval_s=0.05)
    mon.start()
    mon.start()                            # idempotent
    import time
    time.sleep(0.2)
    mon.stop()
    assert mon._thread is None
    assert mon.snapshot()                  # at least one pass recorded


def test_monitor_survives_hung_restart():
    """A restart against a wedged chip never returns; the monitor must
    abandon it past restart_timeout_s, keep probing (incl. the healthy
    tier), and not stack a second restart while the first lives."""
    import threading
    import time

    from distributed_llm_tpu.config import tiny_cluster
    from distributed_llm_tpu.serving.health import HealthMonitor
    from distributed_llm_tpu.serving.router import Router

    r = Router(strategy="heuristic", benchmark_mode=True,
               cluster=tiny_cluster())
    mon = HealthMonitor(r, interval_s=0.05, max_consecutive_failures=1,
                        restart_timeout_s=0.2)
    nano_mgr = r.tiers["nano"].server_manager
    nano_mgr.start_server()
    r.tiers["orin"].server_manager.start_server()
    mon.probe_once()                      # both seen running

    hang = threading.Event()

    class WedgedManager:
        def is_server_running(self):
            return True

        def health(self):
            return {"ok": False, "error": "wedged"}

        def stop_server(self):
            pass

        def start_server(self, beat=None):
            hang.wait(30)                 # never returns within the test

    r.tiers["nano"].server_manager = WedgedManager()
    t0 = time.monotonic()
    snap = mon.probe_once()               # triggers the bounded restart
    assert time.monotonic() - t0 < 5, "probe_once hung on the restart"
    assert snap["nano"]["state"] == "failed"
    assert snap["orin"]["state"] == "running"

    # Next probe: restart still in flight — not stacked, probing continues.
    snap2 = mon.probe_once()
    assert snap2["orin"]["state"] == "running"
    assert len([t for t in threading.enumerate()
                if t.name == "restart-nano"]) == 1
    hang.set()                            # release the abandoned worker
    r.tiers["nano"].server_manager = nano_mgr
