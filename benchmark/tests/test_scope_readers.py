"""Device time by named scope (``layer_metrics/scope_readers.py``) on the
recorded second of ``smollm2-1.7b.decode-closed`` with a hand-written
map, and on two synthetic programs that name one operation alike."""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import manifest as mf  # noqa: E402
import tracing  # noqa: E402
from layer_metrics import named_readers, scope_readers  # noqa: E402

# By hand, from the recorded names: a few operations of the tick's layer
# loop and its head; every other operation is in no map (null).
BY_HAND = {"fusion.182": ("ffn", False), "fusion.163": ("head", False),
           "fusion.162": ("sample", False),
           "convolution_convert_fusion.4": ("attention", False),
           "convolution_convert_fusion.5": ("attention", True),
           "multiply_cosine_fusion.2": (None, False)}


def recorded():
    with open(os.path.join(HERE, "tests", "data",
                           "trace_decode_closed_named.json")) as f:
        doc = json.load(f)
    return {"devices": doc["devices"], "t_lo": doc["t_lo"],
            "t_hi": doc["t_hi"]}


def entry(stage, ops, window=256, chunk=None):
    return {"stage": stage, "program": "jit_decode_tick" if stage == "decode"
            else "jit_chunk_prefill", "window_tokens": window,
            "chunk_tokens": chunk, "attention_form": None,
            "built_s": {"lower": 0.5, "compile": 0.25, "read": 0.125},
            "ops": {k: {"scope": s, "mixed": m} for k, (s, m) in ops.items()}}


class Client:
    """The route as the program serves it: ``?ops=0`` lists, ``?stage=``
    and ``?window_tokens=`` select."""

    def __init__(self, doc):
        self.doc, self.asked = doc, []

    def get(self, path):
        route, _, query = path.partition("?")
        assert route == scope_readers.ROUTE
        self.asked.append(query)
        if self.doc is None:
            return types.SimpleNamespace(status_code=404,
                                         get_json=lambda: None)
        args = dict(kv.split("=") for kv in query.split("&") if kv)
        windows = args.get("window_tokens")
        doc = {"tiers": {tier: [
            {k: v for k, v in e.items()
             if args.get("ops") != "0" or k not in ("ops", "built_s")}
            for e in entries
            if args.get("stage") in (None, e["stage"]) and (
                windows is None
                or str(e["window_tokens"]) in windows.split(","))]
            for tier, entries in self.doc["tiers"].items()}}
        return types.SimpleNamespace(status_code=200, get_json=lambda: doc)


def context(trace, doc):
    served = types.SimpleNamespace(
        client=Client(doc),
        entries={"nano": {"tier": {"decode_steps_per_tick": 4}}})
    dev = next(iter(trace["devices"].values()))
    return types.SimpleNamespace(trace=trace, served=served,
                                 tier_traces=lambda tier: [dev])


def whole_op_ns(trace, program):
    """Independently: the non-wrapper operations that start inside a
    whole execution of ``jit_<program>``."""
    dev = next(iter(trace["devices"].values()))
    spans = named_readers.executions(dev, program, trace["t_lo"],
                                     trace["t_hi"])
    total = 0
    for name, start, dur in dev["ops"]:
        if tracing.is_wrapper(name):
            continue
        if any(a <= start < a + d for a, d in spans):
            total += dur
    return len(spans), total


def test_scopes_and_the_unscoped_add_up_to_the_whole_executions():
    trace = recorded()
    ctx = context(trace, {"tiers": {"nano": [
        entry("decode", BY_HAND),
        entry("chunk_prefill", {"copy.70": (None, False)}, chunk=64)]}})
    n, total = whole_op_ns(trace, "decode_tick")
    assert n == 4                     # the fifth is cut by the capture's end
    got = scope_readers.reduce_program(ctx, "nano", "decode_tick")
    assert got["executions"] == n
    assert got["ops_ns"] == total == sum(got["by_scope_ns"].values())
    assert set(got["by_scope_ns"]) == {"ffn", "head", "sample", "attention",
                                       "mixed", "null"}
    per = 1e6 * n * 4
    named = {s: scope_readers.scope_ms(ctx, "nano", "decode_tick", [s])
             for s in ("ffn", "head", "sample", "attention")}
    assert all(v > 0 for v in named.values())
    assert named["head"] + named["sample"] == pytest.approx(
        scope_readers.scope_ms(ctx, "nano", "decode_tick",
                               ["head", "sample", "absent"]))
    share = scope_readers.unscoped_share(ctx, "nano")
    assert sum(named.values()) + share / 100 * total / per == pytest.approx(
        total / per)
    # One rung, the map's own window; the step by name is the programs'.
    (rung,) = got["rungs"]
    assert rung["window_tokens"] == 256 and rung["executions"] == 4
    assert got["modules_ns"] / per == pytest.approx(
        named_readers.decode_step_ms(ctx, "nano"))
    assert got["not_told_apart"] == []
    # The chunk: one whole execution, all of it null under a map that
    # names one copy, and no scope.
    chunk = scope_readers.reduce_program(ctx, "nano", "chunk_prefill")
    assert chunk["executions"] == 1
    assert chunk["by_scope_ns"] == {"null": whole_op_ns(
        trace, "chunk_prefill")[1]}
    # A listing and one request a program kind, whatever is read after.
    assert ctx.served.client.asked == [
        "ops=0&stage=decode", "stage=decode&window_tokens=256",
        "ops=0&stage=chunk_prefill", "stage=chunk_prefill&window_tokens=256"]
    assert got["not_asked"] == [] and ctx._scope_asked["built_s"] == {
        "lower": 1.0, "compile": 0.5, "read": 0.25}


def test_a_cut_execution_is_left_out():
    trace = recorded()
    dev = trace["devices"]["0"]
    ctx = context(trace, {"tiers": {"nano": [entry("decode", BY_HAND)]}})
    assert len(named_readers.executions(dev, "decode_tick")) == 5
    got = scope_readers.reduce_program(ctx, "nano", "decode_tick")
    assert got["executions"] == 4
    last = max(m[1] for m in dev["modules"] if "decode_tick" in m[0])
    inside_cut = sum(d for name, s, d in dev["ops"]
                     if s >= last and not tracing.is_wrapper(name))
    assert inside_cut > 0
    assert got["ops_ns"] == whole_op_ns(trace, "decode_tick")[1]


@pytest.mark.parametrize("doc", [None, {"tiers": {}},
                                 {"tiers": {"nano": []}}],
                         ids=["404", "no tier", "no program"])
def test_none_without_a_map(doc):
    ctx = context(recorded(), doc)
    assert scope_readers.scope_ms(ctx, "nano", "decode_tick",
                                  ["attention"]) is None
    assert scope_readers.scope_ms(ctx, "nano", "chunk_prefill",
                                  ["attention"]) is None
    assert scope_readers.unscoped_share(ctx, "nano") is None


def test_none_without_a_whole_execution_or_such_a_program():
    trace = recorded()
    ctx = context(trace, {"tiers": {"nano": [entry("decode", BY_HAND)]}})
    # The tier compiled no chunk program the map knows.
    assert scope_readers.scope_ms(ctx, "nano", "chunk_prefill",
                                  ["attention"]) is None
    dev = trace["devices"]["0"]
    empty = dict(trace, devices={"0": {"ops": dev["ops"], "modules": []}})
    ctx = context(empty, {"tiers": {"nano": [entry("decode", BY_HAND)]}})
    assert scope_readers.unscoped_share(ctx, "nano") is None


def two_programs():
    """Two tick programs, 1 ms each, three executions of each between
    two cut ones; both run ``fusion.1`` (400 us); the first also
    ``fusion.2`` (50 us), the second ``fusion.9`` (100 us)."""
    ms = 1_000_000
    ops, modules = [], []
    for i in range(8):
        name = f"jit_decode_tick({1 + i % 2})"
        start = i * 2 * ms
        modules.append([name, start, ms])
        ops.append(["while.3", start, ms])
        ops.append(["fusion.1", start + 1000, 400_000])
        ops.append(["fusion.9@tpu_custom_call", start + 500_000, 100_000]
                   if i % 2 else ["fusion.2", start + 500_000, 50_000])
    return {"devices": {"0": {"ops": ops, "modules": modules}},
            "t_lo": 0, "t_hi": modules[-1][1] + ms}


@pytest.mark.parametrize("alike", [False, True],
                         ids=["told apart", "named alike"])
def test_two_programs_with_different_maps_for_one_name_are_kept_apart(alike):
    """Program 1 runs ``fusion.1`` and ``fusion.2``, program 2
    ``fusion.1`` and ``fusion.9``; their maps file ``fusion.1`` under
    different scopes.  ``alike``: program 1's map also names ``fusion.9``,
    so both name two of program 2's operations."""
    trace = two_programs()
    one = entry("decode", {"fusion.1": ("attention", False),
                           "fusion.2": ("kv_write", False),
                           **({"fusion.9": ("head", False)} if alike
                              else {})}, window=256)
    two = entry("decode", {"fusion.1": ("ffn", False),
                           "fusion.9": ("head", False)}, window=1024)
    ctx = context(trace, {"tiers": {"nano": [one, two]}})
    got = scope_readers.reduce_program(ctx, "nano", "decode_tick")
    # Program 1 ran at 0, 4, 8, 12 ms (the first cut), program 2 at 2,
    # 6, 10, 14 (the last cut): three whole executions each.
    assert got["executions"] == 6
    assert [(r["traced_as"], r["window_tokens"], r["executions"])
            for r in got["rungs"]] == [
        ("jit_decode_tick(1)", 256, 3), ("jit_decode_tick(2)", 1024, 3)]
    assert got["by_scope_ns"] == {
        "attention": 3 * 400_000, "kv_write": 3 * 50_000,
        "ffn": 3 * 400_000, "head": 3 * 100_000}
    assert got["rungs"][0]["by_scope_ms"] == {"attention": 0.4,
                                              "kv_write": 0.05}
    assert scope_readers.scope_ms(ctx, "nano", "decode_tick",
                                  ["attention"]) == pytest.approx(
        3 * 0.4 / (6 * 4))
    # The rungs are asked about from the widest down: 1024 names all of
    # program 2 and not all of program 1, so 256 is asked about too.
    assert ctx.served.client.asked[1:] == [
        "stage=decode&window_tokens=1024", "stage=decode&window_tokens=256"]
    # Named alike, program 2 is filed under the widest of the two and the
    # reader says which rungs it could not tell apart, and that their
    # maps do not agree there.
    assert got["not_told_apart"] == ([{"windows": [256, 1024],
                                       "maps_agree": False}] if alike
                                     else [])


def test_programs_that_hold_the_same_names_are_parted_by_their_order():
    """Both maps name ``fusion.1``, ``fusion.2`` and ``fusion.9``; the
    trace runs ``fusion.1`` first, as the 256 rung's text has it and the
    1024 rung's has not."""
    trace = two_programs()
    narrow = entry("decode", {"fusion.1": ("attention", False),
                              "fusion.2": ("kv_write", False),
                              "fusion.9": ("head", False)}, window=256)
    wide = entry("decode", {"fusion.9": ("ffn", False),
                            "fusion.2": ("ffn", False),
                            "fusion.1": ("ffn", False)}, window=1024)
    ctx = context(trace, {"tiers": {"nano": [narrow, wide]}})
    got = scope_readers.reduce_program(ctx, "nano", "decode_tick")
    assert got["not_told_apart"] == []
    assert {r["window_tokens"] for r in got["rungs"]} == {256}
    assert "ffn" not in got["by_scope_ns"]


def test_narrower_rungs_are_not_asked_about_once_every_program_is_named():
    trace = two_programs()
    ops = {"fusion.1": ("ffn", False), "fusion.2": ("kv_write", False),
           "fusion.9": ("head", False)}
    ctx = context(trace, {"tiers": {"nano": [
        entry("decode", ops, window=w) for w in (64, 256, 1024)]}})
    got = scope_readers.reduce_program(ctx, "nano", "decode_tick")
    assert ctx.served.client.asked == ["ops=0&stage=decode",
                                       "stage=decode&window_tokens=1024"]
    assert got["not_asked"] == [256, 64]
    assert {r["window_tokens"] for r in got["rungs"]} == {1024}


def test_the_chunk_programs_the_run_used_are_asked_about_first():
    """Rungs 256 and 1024 ran lane chunks in the run, 4096 none: the
    first request names the two, and where they name every traced
    program no second one is made."""
    trace = recorded()
    ops = {"copy.70": ("layer_scan", False)}
    ctx = context(trace, {"tiers": {"nano": [
        entry("chunk_prefill", ops, window=w, chunk=64)
        for w in (256, 1024, 4096)]}})
    ctx.stats_before = {"tiers": {"nano": {"prefill": {"chunks_by_window": {
        "256": 3, "1024": 3, "4096": 1}}}}}
    ctx.stats_after = {"tiers": {"nano": {"prefill": {"chunks_by_window": {
        "256": 9, "1024": 4, "4096": 1}}}}}
    got = scope_readers.reduce_program(ctx, "nano", "chunk_prefill")
    assert ctx.served.client.asked == [
        "ops=0&stage=chunk_prefill",
        "stage=chunk_prefill&window_tokens=1024,256",
        "stage=chunk_prefill&window_tokens=4096"]
    # One operation of many is named: the others are asked about too,
    # and the reader says it could not tell the three apart.
    assert got["not_asked"] == []
    assert got["not_told_apart"] == [{"windows": [256, 1024, 4096],
                                      "maps_agree": True}]
    dev = trace["devices"]["0"]
    (_, lo, dur), = [m for m in dev["modules"] if "chunk_prefill" in m[0]]
    full = {name.split("@")[0]: ("layer_scan", False)      # in its order
            for name, start, _ in sorted(dev["ops"], key=lambda e: e[1])
            if lo <= start < lo + dur}
    ctx = context(trace, {"tiers": {"nano": [
        entry("chunk_prefill", full, window=w, chunk=64)
        for w in (256, 1024, 4096)]}})
    ctx.stats_before, ctx.stats_after = {}, {"tiers": {"nano": {"prefill": {
        "chunks_by_window": {"256": 6}}}}}
    got = scope_readers.reduce_program(ctx, "nano", "chunk_prefill")
    assert ctx.served.client.asked[1:] == [
        "stage=chunk_prefill&window_tokens=256"]
    assert got["not_asked"] == [4096, 1024]


def test_every_new_metric_resolves_through_the_manifest():
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]
             if ".scope_" in m["name"] or ".chunk_scope_" in m["name"]]
    assert len(names) == 8
    cells = {c["name"] for c in manifest["workloads"]}
    for m in manifest["per_layer"]:
        if m["name"] not in names:
            continue
        assert set(m["workloads"]) <= cells
        spec = mf.load_json("layer_metrics", m["name"] + ".json")
        assert spec["unit"] == m["unit"] and spec["moves"] == m["moves"]
        assert spec["source"] == m["source"] == "device_trace"
        assert callable(mf.load_callable(spec["reader"], "layer_metrics"))
