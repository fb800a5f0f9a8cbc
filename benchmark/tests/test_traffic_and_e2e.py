"""The generator gives two seeds the same work; the window's edges."""
import collections
import itertools
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
import e2e  # noqa: E402
import trafficgen  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic")))


def mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def stream(m, seed, count):
    return list(itertools.islice(trafficgen.iter_requests(m, seed, 0), count))


def test_two_seeds_ask_for_the_same_multiset_of_lengths():
    for name in MIXES:
        m = mix(name)
        n = len(trafficgen.expand_grid(m))
        a = stream(m, 11, 3 * n)
        b = stream(m, 3_000_000_019, 3 * n)
        count = lambda reqs: collections.Counter(
            (r["class"], r["tokens"]) for r in reqs)
        assert count(a) == count(b)
        assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
        # Exactly the stated number of tokens: BOS + "user: " + message.
        assert all(len(r["message"]) + trafficgen.CHAT_OVERHEAD_TOKENS
                   == r["tokens"] for r in a)
        assert all(r["message"] == r["message"].strip() for r in a)
        assert len({r["message"] for r in a}) == len(a)     # distinct
        assert a == stream(m, 11, 3 * n)


def rec(stamps, due=0.0, ok=True):
    return {"stamps": stamps, "due": due, "ok": ok}


def test_tokens_per_s_counts_stamps_inside_the_window_only():
    t0, seconds, eps = 100.0, 40.0, 1e-6
    records = [rec([t0 - eps, t0, t0 + 1, t0 + seconds - eps,
                    t0 + seconds], due=t0 - 5)]
    assert e2e.tokens_in_window(records, t0, seconds) == 3
    spec = {"kind": "token_rate"}
    assert e2e.compute(spec, records, t0, seconds, 0.0) == 3 / 40.0


def test_latency_is_sent_to_last_token_and_its_median_ignores_a_stalled_few():
    # 9 replies of 1.4 s and one that a 5 s stall of the machine reached
    records = [rec([k + 0.2, k + 1.4], due=float(k)) for k in range(9)]
    records.append(rec([9.2, 15.4], due=9.0))
    records.append(rec([], due=5.0, ok=False))       # failed: no latency
    spec = {"kind": "percentile", "of": "latency_ms", "q": 50}
    assert abs(e2e.compute(spec, records, 0.0, 50.0, 0.0) - 1400.0) < 1e-6
    assert abs(e2e.latency_ms(records[9]) - 6400.0) < 1e-6
    assert e2e.latency_ms(records[10]) is None


def test_latencies_are_of_requests_due_in_the_window_that_finished():
    t0 = 10.0
    records = [rec([11.0, 11.5, 12.0, 12.5], due=10.5),    # in: 500 ms
               rec([9.9, 10.2, 10.3], due=9.0),             # due before t0
               rec([13.0, 13.1, 13.2], due=12.0, ok=False),  # failed
               rec([21.0, 22.0, 23.0, 24.0], due=19.99),    # in: 1000 ms
               rec([15.0, 15.2], due=14.0)]     # in, too short for a tpot
    ttft = {"kind": "percentile", "of": "ttft_ms", "q": 50}
    assert abs(e2e.compute(ttft, records, t0, 10.0, 0.0) - 1000.0) < 1e-6
    tpot = {"kind": "percentile", "of": "tpot_ms", "q": 90}
    got = e2e.compute(tpot, records, t0, 10.0, 0.0)
    assert abs(got - (500.0 + 0.9 * 500.0)) < 1e-9


def test_tpot_is_the_generation_time_whatever_the_edge_holds_back():
    """A reply of 32 tokens at 4 a tick of 160 ms: 40 ms a token.  The
    edge holds back the last ``held`` characters and hands them over with
    the closing burst; the old (last - first) / (n - 1) read low by the
    held share."""
    for held in (0, 3, 11):
        stamps, shown = [], 0
        for tick in range(1, 9):
            upto = max(0, 4 * tick - held)
            stamps += [0.160 * tick + 1e-5 * i for i in range(upto - shown)]
            shown = max(shown, upto)
        stamps += [0.160 * 8 + 2e-4] * (32 - shown)       # the closing flush
        assert len(stamps) == 32
        assert abs(e2e.tpot_ms(rec(stamps)) - 40.0) < 0.05, held
    assert e2e.tpot_ms(rec([1.0, 1.0, 1.2, 1.2])) is None   # two bursts


def test_percentile_interpolates():
    assert e2e.percentile([1, 2, 3, 4], 50) == 2.5
    assert e2e.percentile([5], 90) == 5
    assert e2e.percentile([], 90) is None


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_names_units_and_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in b["workloads"]}
    e2e_names = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json"))
    for c in b["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for kind in ("end_to_end", "per_layer"):
        for m in b[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert set(m.get("workloads", [])) <= cells
            folder = "end_to_end" if kind == "end_to_end" \
                else "layer_metrics"
            assert os.path.isfile(os.path.join(HERE, folder,
                                               m["name"] + ".json")), m
    for m in b["per_layer"]:
        assert m["moves"] in e2e_names and len(m["layer"]) <= 200
        with open(os.path.join(HERE, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["layer"], spec["unit"], spec["moves"]) == (
            m["layer"], m["unit"], m["moves"]), m["name"]
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    assert len(json.dumps(b)) < 64 * 1024
