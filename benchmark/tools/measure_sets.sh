#!/bin/bash
# Two sets of runs of one cell, the same seeds in both sets, each run a
# new process; one line per run goes to chiprun_out/sets-<cell>.jsonl, each
# run's output to chiprun_out/<cell>-set<k>-<seed>.log, and what stalled the
# host in the window (hoststalls.py) is shown beside the run's numbers.
#   bash benchmark/tools/measure_sets.sh <cell> <seconds> <seed> <seed> ...
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
for set in 1 2; do
  for seed in "$@"; do
    log="chiprun_out/$cell-set$set-$seed.log"
    python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 > "$log" 2>&1
    rc=$?
    line=$(grep '^{' "$log" | tail -1)
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": ${line:-null}}" >> "chiprun_out/sets-$cell.jsonl"
    echo "set $set seed $seed rc $rc $(grep 'bench:correct\|bench:window\] [0-9]' "$log" | cut -c1-160 | tr '\n' ' ') $line" | cut -c1-900
    grep 'bench:host\] \(heartbeat\|machine\)' "$log" | sed 's/asking to sleep .* late or more//' | cut -c1-260
  done
done
