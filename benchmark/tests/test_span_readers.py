"""The host timeline put on the device's clock: enclosure alignment,
idle gaps by phase, by-name against by-structure classification, and
that every PR 26 metric resolves through the manifest."""
import json
import os
import random
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
import manifest as mf  # noqa: E402
import tracing  # noqa: E402
from layer_metrics import named_readers, span_readers  # noqa: E402

NEW_METRICS = {
    "smollm2-1.7b.decode-closed": [
        "sched.host_self_ms_per_tick.nano",
        "device.gap_ms_per_tick.fetch.nano",
        "device.gap_ms_per_tick.account.nano",
        "device.gap_ms_per_tick.emit.nano",
        "device.gap_ms_per_tick.admit.nano",
        "device.gap_ms_per_tick.prepare.nano",
        "device.gap_unattributed_share.nano",
        "step.decode_ms_by_name.nano"],
    "smollm2-1.7b.long-prompt": [
        "step.chunk_prefill_ms.nano",
        "admission.prefill_wait_ms_mean.nano",
        "admission.lane_wait_ms_mean.nano",
        "edge.first_delta_hold_ms_mean"],
}


def data(name):
    with open(os.path.join(HERE, "tests", "data", name)) as f:
        return json.load(f)


# -- a synthetic run: irregular ticks, a known clock offset ---------------------

def synthetic(offset_s=1234.5678, n=24, seed=7, fetch_lag_s=60e-6):
    """Scheduler passes of irregular length on ``perf_counter`` and the
    device trace they would leave, ``offset_s`` apart.  Per pass:
    admit (some passes), prepare > table_upload, decode > dispatch +
    fetch, account, emit; the device runs from shortly after the
    dispatch began until ``fetch_lag_s`` before the fetch returns."""
    rng = random.Random(seed)
    slices, ops, modules = [], [], []
    t = offset_s + 0.010
    want = {g: 0.0 for g in span_readers.GAP_GROUPS}
    busy_until = None

    def host(name, start, dur):
        slices.append((name, start, start + dur))
        return start + dur

    for i in range(n):
        t0 = t
        if i % 4 == 1:                       # an admission: host only here
            t = host("admit", t, 0.0015)
            want["admit"] += 0.0015
        p0 = t
        t = host("table_upload", t + 0.0002, 0.0003)
        t = host("prepare", p0, t - p0 + 0.0004)
        want["prepare"] += t - p0
        d0 = t
        t = host("dispatch", t, 0.0005)
        dev_start = d0 + 0.0002                     # launched mid-dispatch
        want["prepare"] += 0.0002
        dev_dur = 0.150 + rng.choice((0.0, 0.004, 0.031, 0.077))
        f0 = t
        t = host("fetch", f0, dev_start + dev_dur + fetch_lag_s - f0)
        want["fetch"] += fetch_lag_s
        host("decode", d0, t - d0)
        t = host("account", t, 0.0009)
        want["account"] += 0.0009
        t = host("emit", t, 0.0012)
        want["emit"] += 0.0012
        slices.append(("tick", t0, t))
        t += 0.0001                                  # between passes: no slice
        s_ns = round((dev_start - offset_s) * 1e9)
        d_ns = round(dev_dur * 1e9)
        modules.append([f"jit_decode_tick({i % 2})", s_ns, d_ns])
        ops.append(["while.1", s_ns, d_ns])
    dev = {"ops": ops, "modules": modules}
    t_lo = ops[0][1]
    t_hi = ops[-1][1] + ops[-1][2]
    # Idle between the first and the last device event: all but the
    # first pass's lead-in and the last pass's tail.
    return dev, slices, t_lo, t_hi, want


def test_alignment_recovers_a_known_offset_from_irregular_ticks():
    offset = 1234.5678
    dev, slices, t_lo, t_hi, _ = synthetic(offset, fetch_lag_s=60e-6)
    execs = named_readers.executions(dev, "decode_tick")
    decodes = sorted((a, b) for n, a, b in slices if n == "decode")
    # The coarse clock is off by most of a tick either way.
    for coarse in (offset - 0.120, offset + 0.090, offset):
        got, inside = span_readers.align(execs, decodes, coarse)
        # Early by the smallest fetch lag, as the module says.
        assert got == pytest.approx(offset + 60e-6, abs=2e-6)
        assert inside == 1.0
    # Executions missing at either end of the capture still pair.
    got, _ = span_readers.align(execs[3:-2], decodes, offset - 0.1)
    assert got == pytest.approx(offset + 60e-6, abs=2e-6)


def test_alignment_refuses_a_wrong_pairing():
    offset = 1234.5678
    dev, slices, *_ = synthetic(offset)
    execs = named_readers.executions(dev, "decode_tick")
    decodes = sorted((a, b) for n, a, b in slices if n == "decode")
    # Only pairings that are off by two ticks or more are on offer: the
    # irregular ticks cannot all sit inside another tick's slice.
    assert span_readers.align(execs[2:-2], decodes[:-6], offset,
                              max_shift=0) is None
    assert span_readers.align(execs[:10], decodes[4:], offset + 0.7,
                              max_shift=1) is None
    assert span_readers.align([], decodes, offset) is None
    assert span_readers.align(execs, [], offset) is None


def test_gaps_split_by_phase_and_add_up():
    offset = 77.25
    dev, slices, t_lo, t_hi, want = synthetic(offset, fetch_lag_s=0.0)
    table = span_readers.reduce_span(dev, slices, t_lo, t_hi, offset - 0.05)
    # The first execution begins with the capture: its tick started
    # before the span.
    assert table["inside"] == 1.0 and table["ticks"] == 23
    assert table["offset_s"] == pytest.approx(offset, abs=2e-6)
    idle_s = (t_hi - t_lo - tracing.union_ns(dev["ops"], t_lo, t_hi)) / 1e9
    assert table["idle"] == pytest.approx(idle_s, rel=1e-9)
    groups = sum(table[g] for g in span_readers.GAP_GROUPS)
    assert groups + table["unattributed"] == pytest.approx(idle_s, rel=1e-9)
    # 23 whole host stretches lie between the first and the last device
    # event (the first pass's lead-in and the last pass's tail do not).
    per_pass = {g: v / 24 for g, v in want.items()}
    assert table["account"] == pytest.approx(23 * per_pass["account"],
                                             rel=1e-6)
    assert table["emit"] == pytest.approx(23 * per_pass["emit"], rel=1e-6)
    assert table["admit"] == pytest.approx(6 * 0.0015, rel=1e-6)
    assert table["prepare"] == pytest.approx(23 * (0.0009 + 0.0002),
                                             rel=1e-6)
    assert table["fetch"] == pytest.approx(0.0, abs=1e-9)
    # Between passes no slice is open.
    assert table["unattributed"] == pytest.approx(23 * 0.0001, rel=1e-6)


def test_executions_cut_by_the_captures_edges_are_left_out():
    dev = {"modules": [["jit_decode_tick(1)", 1000, 12_000_000],
                       ["jit_decode_tick(1)", 20_000_000, 158_000_000],
                       ["jit_chunk_prefill(2)", 180_000_000, 90_000_000],
                       ["jit_decode_tick(1)", 280_000_000, 158_000_000],
                       ["jit_decode_tick(1)", 440_000_000, 60_000_000],
                       ["jit_copy_block(3)", 100, 50]]}
    t_lo, t_hi = 1000, 500_000_000
    assert len(named_readers.executions(dev, "decode_tick")) == 4
    assert named_readers.executions(dev, "decode_tick", t_lo, t_hi) == [
        [20_000_000, 158_000_000], [280_000_000, 158_000_000]]
    assert named_readers.executions(dev, "chunk_prefill", t_lo, t_hi) == [
        [180_000_000, 90_000_000]]
    assert named_readers.executions(dev, "copy_block") == []   # under MIN_NS


def test_self_intervals_partition_nested_slices():
    got = span_readers.self_intervals([
        ("tick", 0.0, 10.0), ("admit", 1.0, 4.0), ("prefill", 2.0, 3.0),
        ("decode", 5.0, 9.0), ("dispatch", 5.0, 6.0), ("fetch", 6.0, 9.0)])
    assert got == [("admit", 1.0, 2.0), ("prefill", 2.0, 3.0),
                   ("admit", 3.0, 4.0), ("dispatch", 5.0, 6.0),
                   ("fetch", 6.0, 9.0)]
    assert span_readers.split_idle([(0.5, 2.5), (8.0, 12.0)], got) == {
        None: 0.5 + 3.0, "admit": 1.0, "prefill": 0.5, "fetch": 1.0}


# -- a second of decode-closed recorded with the new names ----------------------

def test_by_name_and_by_structure_agree_on_the_recorded_second():
    rec = data("trace_decode_closed_named.json")
    dev = rec["devices"]["0"]
    by_structure = [(m[1], m[2]) for m in tracing.classify(dev)
                    if m[0] == "decode"]
    by_name = [tuple(e) for e in named_readers.executions(dev,
                                                          "decode_tick")]
    assert by_name and by_name == sorted(by_structure)
    prefill = sorted((m[1], m[2]) for m in tracing.classify(dev)
                     if m[0] == "prefill")
    chunks = [tuple(e) for e in named_readers.executions(dev,
                                                         "chunk_prefill")]
    assert chunks == prefill
    assert not any(m[0].startswith("jit_run") for m in dev["modules"])


def test_recorded_second_aligns_and_its_gaps_add_up():
    rec = data("trace_decode_closed_named.json")
    dev = rec["devices"]["0"]
    slices = span_readers.tier_slices(rec["debug_trace"], "nano")
    table = span_readers.reduce_span(
        dev, slices, rec["t_lo"], rec["t_hi"], rec["coarse_offset_s"])
    assert table is not None and table["inside"] == 1.0
    idle_s = (rec["t_hi"] - rec["t_lo"] - tracing.union_ns(
        dev["ops"], rec["t_lo"], rec["t_hi"])) / 1e9
    groups = sum(table[g] for g in span_readers.GAP_GROUPS)
    assert groups + table["unattributed"] == pytest.approx(idle_s, rel=1e-6)
    assert table["unattributed"] < 0.10 * idle_s
    # The exact offset, not the coarse one, is what encloses the ticks.
    execs = named_readers.executions(dev, "decode_tick")
    decodes = sorted((a, b) for n, a, b in slices if n == "decode")
    for s, d in execs:
        a, b = next((a, b) for a, b in decodes
                    if a <= s / 1e9 + table["offset_s"] <= b)
        assert (s + d) / 1e9 + table["offset_s"] <= b + 1e-9


# -- the manifest ---------------------------------------------------------------

def test_every_new_metric_resolves_through_the_manifest():
    manifest = mf.load_manifest()
    for cell, names in NEW_METRICS.items():
        reported = {m["name"]: m for m in
                    mf.metrics_for(manifest, cell, "per_layer")}
        e2e_of_cell = {m["name"] for m in
                       mf.metrics_for(manifest, cell, "end_to_end")}
        for name in names:
            entry = reported[name]
            spec = mf.load_json("layer_metrics", name + ".json")
            assert callable(mf.load_callable(spec["reader"],
                                             "layer_metrics"))
            for key in ("unit", "better", "source", "layer", "moves"):
                assert spec[key] == entry[key], (name, key)
            assert entry["workloads"] == [cell]
            assert entry["moves"] in e2e_of_cell
    assert sum(len(v) for v in NEW_METRICS.values()) == 12


def test_readers_find_nothing_on_a_program_without_the_timeline():
    """The parent commit: all programs ``jit_run``, ``/debug/trace``
    without metadata, no new counters — every reader returns None and
    raises nothing."""
    import types
    old = data("trace_decode_closed.json")
    served = types.SimpleNamespace(
        entries={"nano": {"tier": {"decode_steps_per_tick": 4}}},
        get_json=lambda path: {"traceEvents": [], "displayTimeUnit": "ms"})
    ctx = types.SimpleNamespace(
        served=served, trace=old, host_span=(10.0, 11.0), wall_offset=5.0,
        metrics_before="", metrics_after="dllm_queue_wait_ms_count"
        '{tier="nano"} 3\n',
        tier_traces=lambda tier: [old["devices"]["0"]],
        host_time=lambda ns: 10.0 + ns / 1e9)
    assert named_readers.decode_step_ms(ctx, "nano") is None
    assert named_readers.chunk_prefill_ms(ctx, "nano") is None
    assert span_readers.gap_ms_per_tick(ctx, "nano", "fetch") is None
    assert span_readers.gap_unattributed_share(ctx, "nano") is None
    assert span_readers.host_self_ms_per_tick(ctx, "nano") is None
    assert span_readers.histogram_mean(
        ctx, "dllm_prefill_wait_ms", "nano") is None
    assert span_readers.histogram_mean(
        ctx, "dllm_first_delta_hold_ms") is None


def test_counter_readers_take_deltas_of_the_run():
    import types
    before = ('dllm_tick_phase_ms_total{tier="nano",phase="emit"} 10\n'
              'dllm_tick_phase_ms_total{tier="nano",phase="fetch"} 1000\n'
              'dllm_decode_ticks_total{tier="nano",kind="paged_decode",'
              'impl="xla"} 10\n'
              'dllm_first_delta_hold_ms_sum{strategy="token"} 100\n'
              'dllm_first_delta_hold_ms_count{strategy="token"} 1\n')
    after = ('dllm_tick_phase_ms_total{tier="nano",phase="emit"} 40\n'
             'dllm_tick_phase_ms_total{tier="nano",phase="account"} 15\n'
             'dllm_tick_phase_ms_total{tier="nano",phase="fetch"} 9000\n'
             'dllm_tick_phase_ms_total{tier="nano",phase="idle_wait"} 70\n'
             'dllm_tick_phase_ms_total{tier="orin",phase="emit"} 999\n'
             'dllm_decode_ticks_total{tier="nano",kind="paged_decode",'
             'impl="xla"} 20\n'
             'dllm_decode_ticks_total{tier="nano",kind="ragged_decode",'
             'impl="pallas"} 5\n'
             'dllm_first_delta_hold_ms_sum{strategy="token"} 700\n'
             'dllm_first_delta_hold_ms_count{strategy="token"} 3\n'
             'dllm_first_delta_hold_ms_sum{strategy="hybrid"} 300\n'
             'dllm_first_delta_hold_ms_count{strategy="hybrid"} 1\n')
    ctx = types.SimpleNamespace(metrics_before=before, metrics_after=after)
    # (30 emit + 15 account) host ms over 15 ticks; fetch and idle_wait
    # wait for the device or for work.
    assert span_readers.host_self_ms_per_tick(ctx, "nano") == 3.0
    assert span_readers.histogram_mean(
        ctx, "dllm_first_delta_hold_ms") == 300.0


# -- the whole control flow on CPU ----------------------------------------------

@pytest.mark.parametrize("cell", sorted(NEW_METRICS))
def test_rehearsal_lists_the_new_counter_metrics(cell):
    """``--rehearse`` on CPU: no device plane, so the trace readers find
    nothing; the metrics read from the program's counters print."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", "5", "--seconds", "6", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("rehearsal: "))
    names = set(json.loads(line[len("rehearsal: "):])["metric_names"])
    counters = [n for n in NEW_METRICS[cell]
                if mf.load_json("layer_metrics", n + ".json")["source"]
                == "program_counter"]
    assert counters and set(counters) <= names, (counters, names)
