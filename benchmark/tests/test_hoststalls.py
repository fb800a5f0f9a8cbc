"""The stall recorders tell a stall of this process from one of the
machine, leave no process behind, and the median reader reads a median."""
import gc
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "layer_metrics"))
import hoststalls  # noqa: E402
import client_readers  # noqa: E402


def hold_the_gil(seconds):
    # A C call that keeps the GIL for its whole length: what a stall of
    # THIS process looks like to its other threads.
    n = 1
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        sum(range(200_000 * n))


def test_a_stall_of_this_process_is_not_the_machines():
    wall_offset = time.time() - time.perf_counter()
    with hoststalls.recording() as rec:
        _, beat, machine = rec
        t0 = time.perf_counter()
        time.sleep(0.15)
        old = sys.getswitchinterval()
        sys.setswitchinterval(10.0)       # nobody takes the GIL from us
        try:
            hold_the_gil(0.3)
        finally:
            sys.setswitchinterval(old)
        gc.collect()
        time.sleep(0.15)
        proc = machine._proc
        assert proc.poll() is None        # the other process is running
    assert proc.poll() is not None        # ... and was waited for
    lines = hoststalls.summary(rec, t0, time.perf_counter() - t0,
                               wall_offset)
    assert set(lines) == {"gc", "heartbeat", "machine"}
    late = [ms for at, ms in beat.late if at >= t0]
    assert late and max(late) >= 200.0, beat.late
    # The machine did not stand still for 200 ms while we held the GIL.
    assert not [ms for _, ms in machine.late if ms >= 200.0], machine.late
    assert "gen 2: 1 taking" in lines["gc"]
    assert " 0 times" not in lines["heartbeat"]


def test_recorders_stop_when_the_traffic_raises():
    try:
        with hoststalls.recording() as rec:
            proc = rec[2]._proc
            raise RuntimeError("the run failed")
    except RuntimeError:
        pass
    assert proc.poll() is not None
    assert not rec[1]._thread.is_alive()
    assert rec[0]._on not in gc.callbacks


def test_tpot_median_reader_takes_requests_due_in_the_window():
    def rec(tpot_ms, due):
        # bursts 4 tokens apart: first burst, three more, a closing one
        step = tpot_ms * 4 / 1000.0
        stamps = [x for k in range(5) for x in [due + 1 + k * step] * 4]
        return {"stamps": stamps, "due": due, "ok": True}
    ctx = types.SimpleNamespace(
        records=[rec(10.0, 1.0), rec(11.0, 2.0), rec(30.0, 3.0),
                 rec(99.0, 50.0)],                  # due after the window
        t0=0.0, seconds=10.0)
    assert abs(client_readers.tpot_percentile(ctx, 50) - 11.0) < 1e-6
    assert abs(client_readers.tpot_percentile(ctx, 100) - 30.0) < 1e-6
