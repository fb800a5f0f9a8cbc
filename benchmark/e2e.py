"""End-to-end metric arithmetic: from client records to numbers.

Each end-to-end metric is a data file ``end_to_end/<name>.json``:

    {"kind": "percentile", "of": "ttft_ms" | "tpot_ms" | "latency_ms",
     "q": 0..100}
    {"kind": "token_rate"}
    {"kind": "setup"}

Latencies are of requests DUE inside the window (closed loop: when the
client sent it) that ran to their ``done`` event; a request that failed or did not finish misses every latency and
counts in ``failed``.  ``token_rate`` counts every token whose arrival
stamp lies in [t0, t0 + seconds), whether or not its request started or
ended inside the window.

``tpot_ms`` is the time per token while a reply streams.  Tokens reach
the client in bursts (a tick's tokens together), the serving edge holds
the reply's last characters back, and the closing burst hands over what
was held: tokens that were generated earlier.  So the time runs from the
first burst to the last burst BEFORE the closing one, and is divided by
the tokens that arrived after the first burst up to there.  Whatever the
edge holds back, that is the generation time of those tokens.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between order statistics (rank q/100·(n-1));
    None for an empty sample."""
    v = sorted(values)
    if not v:
        return None
    pos = (q / 100.0) * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def in_window(records: List[Dict[str, Any]], t0: float, seconds: float
              ) -> List[Dict[str, Any]]:
    return [r for r in records if t0 <= r["due"] < t0 + seconds]


def ttft_ms(rec: Dict[str, Any]) -> Optional[float]:
    if not rec["ok"] or not rec["stamps"]:
        return None
    return (rec["stamps"][0] - rec["due"]) * 1000.0


BURST_GAP_S = 0.001        # stamps closer than this arrived together


def bursts(stamps: Sequence[float]) -> List[List[float]]:
    """[[arrival of the burst's first token, tokens in it], ...]."""
    out: List[List[float]] = []
    last = None
    for s in stamps:
        if last is None or s - last >= BURST_GAP_S:
            out.append([s, 0])
        out[-1][1] += 1
        last = s
    return out


def tpot_ms(rec: Dict[str, Any]) -> Optional[float]:
    """None for a reply of fewer than three bursts (one that ended within
    two ticks of its first visible token)."""
    if not rec["ok"]:
        return None
    b = bursts(rec["stamps"])
    if len(b) < 3:
        return None
    tokens = sum(n for _, n in b[1:-1])
    return (b[-2][0] - b[0][0]) * 1000.0 / tokens


def latency_ms(rec: Dict[str, Any]) -> Optional[float]:
    """From when the client sent the request to the arrival of the
    reply's last token: what a caller that waits for the whole reply
    waits."""
    if not rec["ok"] or not rec["stamps"]:
        return None
    return (rec["stamps"][-1] - rec["due"]) * 1000.0


QUANTITIES = {"ttft_ms": ttft_ms, "tpot_ms": tpot_ms,
              "latency_ms": latency_ms}


def tokens_in_window(records: List[Dict[str, Any]], t0: float,
                     seconds: float) -> int:
    end = t0 + seconds
    return sum(1 for r in records for s in r["stamps"] if t0 <= s < end)


def compute(spec: Dict[str, Any], records: List[Dict[str, Any]], t0: float,
            seconds: float, setup_s: float) -> Optional[float]:
    kind = spec["kind"]
    if kind == "setup":
        return setup_s
    if kind == "token_rate":
        return tokens_in_window(records, t0, seconds) / seconds
    if kind == "percentile":
        fn = QUANTITIES[spec["of"]]
        vals = [x for x in (fn(r) for r in in_window(records, t0, seconds))
                if x is not None]
        return percentile(vals, float(spec["q"]))
    raise ValueError(f"end-to-end metric kind {kind!r} is not one of "
                     f"percentile, token_rate, setup")
