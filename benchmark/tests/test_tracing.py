"""The trace reduction on hand-made events and on the small recorded
trace (one second of smollm2-1.7b.decode-closed on a v5e)."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import tracing  # noqa: E402


def test_union_and_gaps():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["w", 100, 1]]
    assert tracing.union_ns(ev) == 15 + 5 + 1
    assert tracing.union_ns(ev, 8, 32) == 7 + 2
    assert tracing.gaps_ns(ev, 0, 50) == [(15, 15), (35, 15)]


def test_short_names_and_kinds():
    assert tracing.short_name(
        "%fusion.16 = (u32[1]{0}) fusion(u32[2]{0} %key.1), kind=kLoop"
    ) == "fusion.16"
    assert tracing.short_name(
        '%custom-call.3 = bf16[8]{0} custom-call(), '
        'custom_call_target="tpu_custom_call"'
    ) == "custom-call.3@tpu_custom_call"
    assert tracing.is_wrapper("while.35") and not tracing.is_wrapper("copy.1")


def test_module_kind_from_nested_loops():
    whiles = [["while.35", 10, 80], ["while.36", 12, 20],
              ["while.36", 40, 20], ["while.3", 200, 50]]
    assert tracing.module_kind(["jit_run(1)", 5, 90], whiles) == "decode"
    assert tracing.module_kind(["jit_run(2)", 190, 70], whiles) == "prefill"
    assert tracing.module_kind(["jit_copy_block(3)", 300, 9], whiles) \
        == "other"


def test_recorded_trace():
    with open(os.path.join(HERE, "tests", "data",
                           "trace_decode_closed.json")) as f:
        trace = json.load(f)
    dev = trace["devices"]["0"]
    kinds = [k for k, _, _ in tracing.classify(dev)]
    # Two decode ticks; between them eight admissions, each a block copy
    # and a suffix chunk.
    assert (kinds.count("decode"), kinds.count("prefill"),
            kinds.count("other")) == (2, 8, 8)
    ticks = [m for m in tracing.classify(dev) if m[0] == "decode"]
    step_ms = sum(m[2] for m in ticks) / 1e6 / (len(ticks) * 4)
    assert abs(step_ms - 39.21) < 0.01        # (158.37 + 155.32) / 8
    busy = tracing.union_ns(dev["ops"], trace["t_lo"], trace["t_hi"])
    window = trace["t_hi"] - trace["t_lo"]
    assert 0.90 < busy / window < 1.0
    assert abs(busy / window - EXPECTED_BUSY_SHARE) < 1e-4


EXPECTED_BUSY_SHARE = 0.952734
