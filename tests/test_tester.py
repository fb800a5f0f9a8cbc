"""Benchmark-harness tests: CLI contract, sweep semantics, CSV schemas.

Reference behaviors under test: threshold sweep applies only to the token
strategy (routing_chatbot_tester.py:352-367); cache off→benchmark_mode;
per-query + summary CSV schemas; accuracy vs expected_device labels.
"""

import csv

from distributed_llm_tpu.bench import tester
from distributed_llm_tpu.bench.query_sets import query_sets


def test_normalize_query_set_shapes():
    items = tester.normalize_query_set(
        ["  plain string ", {"query": "labeled", "expected_device": "ORIN"},
         {"text": "alt key", "label": "bogus"}, {"query": "   "}])
    assert [i.text for i in items] == ["plain string", "labeled", "alt key"]
    assert [i.expected_device for i in items] == [None, "orin", None]


def test_grid_sweeps_threshold_only_for_token():
    cfg = tester.RunConfig(
        query_set_name="x", thresholds=[100, 1000, 4000],
        strategies=["token", "heuristic"], cache_modes=["off", "on"],
        fixed_threshold_for_non_token=1000,
        output_csv="", output_per_query_csv="")
    grid = list(tester._experiment_grid(cfg))
    token_runs = [g for g in grid if g[0] == "token"]
    other_runs = [g for g in grid if g[0] != "token"]
    assert len(token_runs) == 6            # 3 thresholds × 2 cache modes
    assert len(other_runs) == 2            # fixed threshold × 2 cache modes
    assert {g[2] for g in other_runs} == {1000}


def test_compute_accuracy_ignores_unlabeled():
    rows = [
        {"expected_device": "nano", "device_used": "nano"},
        {"expected_device": "orin", "device_used": "nano"},
        {"expected_device": None, "device_used": "nano"},
    ]
    assert tester.compute_accuracy(rows) == 0.5
    assert tester.compute_accuracy([{"expected_device": None}]) is None


def test_end_to_end_run_writes_both_csvs(tmp_path):
    out_summary = tmp_path / "summary.csv"
    out_perq = tmp_path / "per_query.csv"
    items = tester.normalize_query_set(query_sets["general_knowledge"][:3])
    cfg = tester.RunConfig(
        query_set_name="general_knowledge",
        thresholds=[1000], strategies=["token", "heuristic"],
        cache_modes=["off"], fixed_threshold_for_non_token=1000,
        output_csv=str(out_summary), output_per_query_csv=str(out_perq),
        telemetry=True)
    rows = tester.run_experiment(items, cfg)
    assert len(rows) == 2 * len(items)

    with open(out_perq) as f:
        per_query = list(csv.DictReader(f))
    assert len(per_query) == 2 * len(items)
    assert set(tester.PER_QUERY_HEADERS) == set(per_query[0].keys())
    assert all(r["device_used"] in ("nano", "orin") for r in per_query)
    assert all(float(r["latency_ms"]) >= 0 for r in per_query)

    with open(out_summary) as f:
        summary = list(csv.DictReader(f))
    assert len(summary) == 2
    assert set(tester.SUMMARY_HEADERS) == set(summary[0].keys())
    for row in summary:
        assert 0.0 <= float(row["routing_accuracy"]) <= 1.0
        assert float(row["req_per_s"]) > 0
        total = (int(row["nano_total_tokens"]) + int(row["orin_total_tokens"]))
        assert total == int(row["overall_total_tokens"])


def test_legacy_tester_writes_v1_schema(tmp_path):
    from distributed_llm_tpu.bench.legacy_tester import ChatbotTester, HEADERS
    out = tmp_path / "final_results.csv"
    t = ChatbotTester(query_sets["personal_health"][:2],
                      context_thresholds=[100], strategy="token")
    results = t.run("personal_health", str(out))
    assert 100 in results
    with open(out) as f:
        rows = list(csv.reader(f))
    assert rows[0] == HEADERS
    assert len(rows) == 2
    assert rows[1][0] == "personal_health"


# -- analysis tooling (results_analysis.ipynb equivalent) --------------------

def test_analysis_report_and_plots(tmp_path):
    out_summary = tmp_path / "summary.csv"
    out_perq = tmp_path / "per_query.csv"
    items = tester.normalize_query_set(query_sets["general_knowledge"][:2])
    cfg = tester.RunConfig(
        query_set_name="general_knowledge",
        thresholds=[100, 1000], strategies=["token"],
        cache_modes=["off"], fixed_threshold_for_non_token=1000,
        output_csv=str(out_summary), output_per_query_csv=str(out_perq),
        telemetry=False)
    tester.run_experiment(items, cfg)

    from distributed_llm_tpu.bench import analysis
    md = tmp_path / "report.md"
    plots = tmp_path / "plots"
    analysis.main(["--summary-csv", str(out_summary),
                   "--per-query-csv", str(out_perq),
                   "--output-md", str(md), "--plots-dir", str(plots)])
    text = md.read_text()
    assert "# Benchmark report" in text
    assert "general_knowledge" in text
    assert "Slowest queries" in text
    pngs = list(plots.glob("*.png"))
    assert pngs, "expected at least one plot"


def test_stats_endpoint_exposes_phases_and_cache():
    from distributed_llm_tpu.serving.app import create_app
    app = create_app()
    c = app.test_client()
    c.post("/chat", json={"message": "hello", "strategy": "heuristic",
                          "session_id": "s-stats"})
    r = c.get("/stats")
    assert r.status_code == 200
    d = r.get_json()
    assert d["strategy"] == "heuristic"
    assert d["sessions"] == 1
    assert set(d["tiers"]) == {"nano", "orin"}
    # Degradation cause in one call (ISSUE 7): per-tier draining flags
    # and the SLO monitor's goodput snapshot ride next to the breaker.
    assert set(d["draining"]) == {"nano", "orin"}
    assert d["draining"]["nano"] is False
    assert d["slo"]["observed_total"] >= 1
    assert "goodput" in d["slo"] and "violations" in d["slo"]
    used = [t for t in d["tiers"].values() if t.get("phases")]
    assert used, "at least one tier should have phase timings"
    phases = used[0]["phases"]
    assert {"tokenize", "prefill", "decode"} <= set(phases)
    assert len(d["devices"]) == 8


def test_long_context_set_straddles_threshold_sweep():
    """The long_context query set exists to de-degenerate the reference's
    signature token-threshold sweep: its query+context
    token counts must straddle the swept 100→4000 range so orin's share
    varies across at least 4 threshold points instead of collapsing to
    zero past 500."""
    from distributed_llm_tpu.bench.query_sets import query_sets
    from distributed_llm_tpu.routing.token_counter import approx_token_count

    items = query_sets["long_context"]
    assert len(items) >= 10
    assert {q["expected_device"] for q in items} == {"nano", "orin"}

    # Simulate the tester's accumulating history: count query + context
    # the way TokenStrategy does.
    context_tokens = 0
    effective = []
    for q in items:
        t = approx_token_count(q["query"])
        effective.append(t + context_tokens)
        context_tokens += t + 10          # + a short assistant reply

    thresholds = (100, 250, 500, 1000, 2000, 4000)
    orin_share = [sum(1 for e in effective if e > thr) / len(effective)
                  for thr in thresholds]
    # Share must actually vary across >=4 swept points and not hit zero
    # until (at least) the top rung.
    assert len(set(orin_share)) >= 4, orin_share
    assert orin_share[0] > orin_share[-1]
    assert orin_share[-2] > 0, orin_share
