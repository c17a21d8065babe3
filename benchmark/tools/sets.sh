#!/bin/bash
# Two sets of runs of one cell, the same seeds in both, every run a new
# process, result lines gathered in chiprun_out/sets_<workload>.jsonl.
#   bash benchmark/tools/sets.sh <workload> <seconds> <runs a set> [traced runs] [base seed]
set -u
w=$1; secs=$2; n=$3; traced=${4:-0}; base=${5:-1900000000}
out=chiprun_out/sets_$w.jsonl; mkdir -p chiprun_out; : > "$out"
for set in 1 2; do
  for i in $(seq 1 "$n"); do
    seed=$((base + 7919 * i))
    s=$(date +%s)
    line=$(python3 benchmark/run.py --workload "$w" --seed "$seed" --seconds "$secs" --trace 0 2>chiprun_out/last_stderr.txt | tail -1)
    rc=$?
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"wall_s\": $(( $(date +%s) - s )), \"line\": ${line:-null}}" | tee -a "$out" | cut -c1-420
    grep "set-up done\|window:\|proved\|passed over\|redrawn\|other tokens" chiprun_out/last_stderr.txt
  done
done
for i in $(seq 1 "$traced"); do
  seed=$((base + 104729 * i))
  s=$(date +%s)
  line=$(python3 benchmark/run.py --workload "$w" --seed "$seed" --seconds "$secs" --trace 1 2>chiprun_out/last_stderr.txt | tail -1)
  rc=$?
  echo "{\"set\": 0, \"seed\": $seed, \"rc\": $rc, \"wall_s\": $(( $(date +%s) - s )), \"line\": ${line:-null}}" | tee -a "$out" | cut -c1-300
done
