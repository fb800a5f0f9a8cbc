"""Who holds the interpreter (PR 41): the readers over the CPU clock
beside the wall clock, the run-queue wait and the edge lanes, against a
recorded ``/metrics`` pair and ``/debug/trace`` document (three streamed
requests on the tiny CPU cluster: counts and host milliseconds of a CPU
run, data for the arithmetic and nothing else), and that every PR 41
metric resolves through the manifest."""
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
import manifest as mf  # noqa: E402
from layer_metrics import host_readers, span_readers  # noqa: E402

from test_span_readers import synthetic  # noqa: E402

CELLS = ["smollm2-1.7b.decode-closed", "xing4.0-29b-a4b.reasoned-reply",
         "nemotron-3-nano-30b-a3b.wide-reasoning",
         "phi-4-mini-flash-reasoning.long-context-reasoning"]
NEW_METRICS = {
    "sched.host_cpu_ms_per_tick.nano": "program_counter",
    "sched.host_off_cpu_ms_per_tick.nano": "program_counter",
    "sched.fetch_cpu_ms_per_tick.nano": "program_counter",
    "edge.awake_cpu_ms_per_tick.nano": "program_counter",
    "edge.wake_lag_ms_mean.nano": "program_counter",
    "edge.tokens_per_wakeup.nano": "program_counter",
    "device.gap_edge_awake_share.nano": "program_span",
}
NEW_FAMILIES = ("dllm_tick_phase_cpu_ms_total",
                "dllm_sched_runqueue_wait_ms_total", "dllm_edge_")


def data(name):
    with open(os.path.join(HERE, "tests", "data", name)) as f:
        return f.read()


def recorded(drop=()):
    """The recorded pair as a reader's context; ``drop``: families the
    program under test does not have."""
    def keep(text):
        return "".join(ln + "\n" for ln in text.splitlines()
                       if not ln.startswith(tuple(drop)))
    return types.SimpleNamespace(
        metrics_before=keep(data("metrics_host_before.txt")),
        metrics_after=keep(data("metrics_host_after.txt")))


def read(ctx, name):
    spec = mf.load_json("layer_metrics", name + ".json")
    return mf.load_callable(spec["reader"], "layer_metrics")(
        ctx, **spec.get("args", {}))


# -- the counters ----------------------------------------------------------------

def test_counter_readers_against_values_computed_by_hand():
    """The pair's growth, read off the two files: 6 decode ticks; self
    wall | self CPU ms of the phases that do not wait for the device:
    account 0.244479 | 0.225328, admit 11.842822 | 9.634015, decode
    0.229064 | 0.126921, dispatch 5.879820 | 1.691280, emit 1.991913 |
    1.633859, prepare 4.666252 | 2.285914, table_upload 0.557960 |
    0.559102 (25.412310 | 16.156419); fetch's CPU 0.672523; run-queue
    wait 0.087971; the lanes: 24 awake slices, 72 tokens, 4.020056 ms of
    CPU (the threads' own between their readings), 21 lags of 37.767018
    ms together."""
    ctx = recorded()
    want = {
        "sched.host_cpu_ms_per_tick.nano": 16.156419 / 6,
        "sched.host_off_cpu_ms_per_tick.nano": (25.412310 - 16.156419) / 6,
        "sched.fetch_cpu_ms_per_tick.nano": 0.672523 / 6,
        "edge.awake_cpu_ms_per_tick.nano": 4.020056 / 6,
        "edge.wake_lag_ms_mean.nano": 37.767018 / 21,
        "edge.tokens_per_wakeup.nano": 72 / 24,
    }
    for name, value in want.items():
        assert read(ctx, name) == pytest.approx(value, rel=1e-6), name
    # No BENCHMARK.json entry (the v5e hosts' kernel has no schedstat, so
    # no cell would report it): the reader is scripts/bench_stats.py's.
    assert host_readers.runqueue_wait_ms_per_tick(ctx, "nano") == \
        pytest.approx(0.087971 / 6, rel=1e-6)


def test_cpu_and_off_cpu_add_up_to_the_host_self_time():
    ctx = recorded()
    cpu = host_readers.host_cpu_ms_per_tick(ctx, "nano")
    off = host_readers.host_off_cpu_ms_per_tick(ctx, "nano")
    whole = span_readers.host_self_ms_per_tick(ctx, "nano")
    assert cpu + off == pytest.approx(whole, rel=1e-12)
    assert 0.0 < cpu < whole
    # A phase that waits for the device is in none of the three.
    assert "fetch" not in host_readers._host_phases(ctx, "nano")
    assert "emit" in host_readers._host_phases(ctx, "nano")


def test_counter_readers_find_nothing_on_the_parents_metrics():
    """A program before PR 41: the same text without the new families."""
    ctx = recorded(drop=NEW_FAMILIES)
    assert span_readers.host_self_ms_per_tick(ctx, "nano") is not None
    for name, source in NEW_METRICS.items():
        if source == "program_counter":
            assert read(ctx, name) is None, name
    assert host_readers.runqueue_wait_ms_per_tick(ctx, "nano") is None
    # Another tier's counters are not this tier's.
    assert host_readers.host_cpu_ms_per_tick(recorded(), "orin") is None
    assert host_readers.tokens_per_wakeup(recorded(), "orin") is None


def test_an_unreadable_schedstat_silences_its_reader_alone():
    ctx = recorded(drop=("dllm_sched_runqueue_wait_ms_total",))
    assert host_readers.runqueue_wait_ms_per_tick(ctx, "nano") is None
    assert read(ctx, "sched.host_off_cpu_ms_per_tick.nano") == pytest.approx(
        (25.412310 - 16.156419) / 6, rel=1e-6)
    assert read(ctx, "edge.tokens_per_wakeup.nano") == pytest.approx(72 / 24)


# -- the edge lanes --------------------------------------------------------------

def test_edge_slices_of_the_recorded_document():
    doc = json.loads(data("debug_trace_edge_lanes.json"))
    lanes = sorted(e["args"]["name"] for e in doc["traceEvents"]
                   if e["ph"] == "M")
    assert lanes == ["edge:nano:0", "edge:nano:1", "edge:nano:2",
                     "tier:nano"]
    awake = host_readers.edge_slices(doc, "nano")
    events = [e for e in doc["traceEvents"] if e["name"] == "edge_awake"]
    assert len(awake) == len(events) == 24
    assert sum(e["args"]["tokens"] for e in events) == 72
    origin = doc["metadata"]["ts_origin_perf_counter_s"]
    assert awake[0][0] == pytest.approx(
        origin + min(e["ts"] for e in events) / 1e6)
    assert all(b > a for a, b in awake)
    # A lane's slices never overlap; the tier's slices are read as before.
    for tid in {e["tid"] for e in events}:
        spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                       if e["tid"] == tid)
        assert all(a_end <= b_start for (_, a_end), (b_start, _)
                   in zip(spans, spans[1:]))
    tier = span_readers.tier_slices(doc, "nano")
    assert {"tick", "decode", "emit"} <= {s[0] for s in tier}
    assert not any(s[0] == "edge_awake" for s in tier)
    # Every slice's first token was stamped inside an emit or an admit
    # slice (the short prompts here go through no chunk).
    causes = [(a, b) for n, a, b in tier if n in ("emit", "admit")]
    for e in events:
        if "wake_lag_ms" in e["args"]:
            t = origin + (e["ts"] - 1e3 * e["args"]["wake_lag_ms"]) / 1e6
            assert any(a - 2e-6 <= t <= b + 2e-6 for a, b in causes), e
    # No lane of another tier, none in a document of the parent's.
    assert host_readers.edge_slices(doc, "orin") is None
    old = json.loads(data("trace_decode_closed_named.json"))["debug_trace"]
    assert host_readers.edge_slices(old, "nano") is None
    assert host_readers.edge_slices({}, "nano") is None


def test_interval_helpers():
    assert host_readers.merged([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0),
                                (2.0, 2.5)]) == [(0.0, 2.5), (3.0, 4.0)]
    assert host_readers.intersect(
        [(0.0, 2.0), (3.0, 5.0)], [(1.0, 3.5), (4.0, 9.0)]) == [
        (1.0, 2.0), (3.0, 3.5), (4.0, 5.0)]
    assert host_readers.intersect([], [(0.0, 1.0)]) == []


def test_awake_share_by_hand():
    phases = [("emit", 0.0, 2.0), ("chunk_prefill", 2.0, 4.0),
              ("admit", 4.0, 5.0), ("prepare", 5.0, 8.0)]
    idle = [(1.0, 3.0), (4.5, 7.0)]
    # Under the groups: emit 1.0 + admit 0.5 + prepare 2.0 = 3.5 (the
    # second under chunk_prefill is under no group).  Awake there:
    # [1.5, 2.0) of emit, [4.5, 4.75) of admit, [6.0, 6.5) of prepare,
    # two lanes open at once counted once: 1.25.
    awake = [(1.5, 2.5), (1.75, 2.25), (4.0, 4.75), (6.0, 6.5)]
    assert host_readers.awake_share(idle, phases, awake) == pytest.approx(
        100.0 * 1.25 / 3.5)
    assert host_readers.awake_share(idle, phases, []) == 0.0
    assert host_readers.awake_share([], phases, awake) is None
    assert host_readers.awake_share([(2.5, 3.0)], phases, awake) is None


def as_doc(slices, awake, origin):
    """A ``/debug/trace`` document of one tier's (name, start, end)
    slices and one edge lane's (start, end) slices."""
    events = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
               "args": {"name": "tier:nano"}}]
    events += [{"name": n, "ph": "X", "pid": 1, "tid": 1,
                "ts": (a - origin) * 1e6, "dur": (b - a) * 1e6, "args": {}}
               for n, a, b in slices]
    if awake is not None:
        events.append({"name": "thread_name", "ph": "M", "pid": 1, "tid": 2,
                       "args": {"name": "edge:nano:0"}})
        events += [{"name": "edge_awake", "ph": "X", "pid": 1, "tid": 2,
                    "ts": (a - origin) * 1e6, "dur": (b - a) * 1e6,
                    "args": {"tokens": 4}} for a, b in awake]
    return {"traceEvents": events,
            "metadata": {"ts_origin_perf_counter_s": origin}}


def span_ctx(awake):
    """The synthetic run of test_span_readers.py with a consumer awake
    through the second half of every ``emit`` slice (0.6 of its 1.2 ms)
    and the ``account`` slice after nothing."""
    offset = 77.25
    dev, slices, t_lo, t_hi, _ = synthetic(offset, fetch_lag_s=0.0)
    if awake == "half of every emit":
        awake = [((a + b) / 2, b) for n, a, b in slices if n == "emit"]
    doc = as_doc(slices, awake, origin=offset)
    return types.SimpleNamespace(
        served=types.SimpleNamespace(get_json=lambda path: doc),
        trace={"t_lo": t_lo, "t_hi": t_hi}, wall_offset=5.0,
        host_span=(offset + t_lo / 1e9, offset + t_hi / 1e9),
        tier_traces=lambda tier: [dev],
        host_time=lambda ns: offset - 0.05 + ns / 1e9)


def test_gap_edge_awake_share_on_the_device_clock():
    ctx = span_ctx("half of every emit")
    table = span_readers._span(ctx, "nano")
    grouped = sum(table[g] for g in span_readers.GAP_GROUPS)
    # 23 whole emit slices of 1.2 ms lie in the device's idle time.
    assert host_readers.gap_edge_awake_share(ctx, "nano") == pytest.approx(
        100.0 * 23 * 0.0006 / grouped, rel=1e-6)
    # No lane (the parent), or no host timeline at all: nothing to read.
    assert host_readers.gap_edge_awake_share(span_ctx(None), "nano") is None
    empty = span_ctx([])
    empty.served = types.SimpleNamespace(
        get_json=lambda path: {"traceEvents": []})
    assert host_readers.gap_edge_awake_share(empty, "nano") is None
    assert read(empty, "device.gap_edge_awake_share.nano") is None


# -- the manifest ---------------------------------------------------------------

def test_every_new_metric_resolves_through_the_manifest():
    manifest = mf.load_manifest()
    assert [m["name"] for m in manifest["per_layer"][-7:]] == list(
        NEW_METRICS)
    for cell in CELLS:
        reported = {m["name"]: m for m in
                    mf.metrics_for(manifest, cell, "per_layer")}
        e2e_of_cell = {m["name"] for m in
                       mf.metrics_for(manifest, cell, "end_to_end")}
        for name, source in NEW_METRICS.items():
            entry = reported[name]
            spec = mf.load_json("layer_metrics", name + ".json")
            assert callable(mf.load_callable(spec["reader"],
                                             "layer_metrics"))
            for key in ("unit", "better", "source", "layer", "moves"):
                assert spec[key] == entry[key], (name, key)
            assert entry["source"] == source
            assert entry["workloads"] == CELLS
            assert entry["moves"] == "latency_p50_ms" and \
                entry["moves"] in e2e_of_cell
    other = {m["name"] for m in mf.metrics_for(
        manifest, "smollm2-1.7b.long-prompt", "per_layer")}
    assert not other & set(NEW_METRICS)


# -- the whole control flow on CPU ----------------------------------------------

def test_rehearsal_lists_the_new_counter_metrics():
    """``--rehearse`` on CPU: no device plane, so the span reader finds
    nothing; the metrics read from the program's counters print."""
    cell = CELLS[0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", "5", "--seconds", "6", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("rehearsal: "))
    names = set(json.loads(line[len("rehearsal: "):])["metric_names"])
    counters = {n for n, source in NEW_METRICS.items()
                if source == "program_counter"}
    assert counters <= names, (counters, names)
    assert "device.gap_edge_awake_share.nano" not in names
