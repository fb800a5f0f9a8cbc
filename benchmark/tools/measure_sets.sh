#!/bin/bash
# Two sets of runs of one cell, the same seeds in both sets, each run a
# new process; one line per run goes to chiprun_out/sets-<cell>.jsonl.
#   bash benchmark/tools/measure_sets.sh <cell> <seconds> <seed> <seed> ...
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
for set in 1 2; do
  for seed in "$@"; do
    python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 > chiprun_out/last.log 2>&1
    rc=$?
    line=$(grep '^{' chiprun_out/last.log | tail -1)
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": ${line:-null}}" >> "chiprun_out/sets-$cell.jsonl"
    echo "set $set seed $seed rc $rc $(grep 'bench:correct\|bench:window\] [0-9]' chiprun_out/last.log | cut -c1-160 | tr '\n' ' ') $line" | cut -c1-900
  done
done
