#!/usr/bin/env python3
"""Medians and spreads of measure_sets.sh's output, by the contract's rule:
spread = (third quartile - first quartile) / median, with Python's
``statistics.quantiles(values, n=4)``; a bound is about five times the
wider of the two sets' spreads."""
import json
import statistics as st
import sys

rows = [json.loads(line) for line in open(sys.argv[1])]
bad = [r for r in rows if r["rc"] or not r["result"]
       or not r["result"]["correct"] or r["result"]["failed"]]
print(f"{len(rows)} runs, {len(bad)} with rc != 0, failures or correct false")
names = sorted({m for r in rows if r["result"] for m in r["result"]["metrics"]})
for m in names:
    med, spr = [], []
    for s in (1, 2):
        v = [r["result"]["metrics"][m]["value"] for r in rows
             if r["set"] == s and r["result"]]
        q = st.quantiles(v, n=4)
        med.append(st.median(v))
        spr.append((q[2] - q[0]) / st.median(v))
        print(f"{m} set {s}: median {st.median(v):.4f} spread {spr[-1]:.5f} "
              f"min {min(v):.4f} max {max(v):.4f} values "
              + " ".join(f"{x:.2f}" for x in v))
    print(f"{m}: sets' medians differ by {abs(med[1] - med[0]) / med[0]:.5f}; "
          f"five times the wider spread = {5 * max(spr):.4f}")
