#!/bin/bash
# How the bounds of BENCHMARK.json were measured (PR 23): for one cell, two
# sets of plain runs with the same seeds (six, or the first N of them),
# then one traced run a set unless the third argument is 0, all in one call
# on the chip, each at BENCHMARK.json's run_seconds:
#     chiprun --chips 1 --timeout 3000 -- bash benchmarks/sets.sh <cell> [N] [0]
# Last lines and records land in chiprun_out/sets/ (git-ignored).
cell=$1
seeds=$(echo 1000000007 2000000011 3000000019 4000000007 123456789 987654321 | cut -d' ' -f1-${2:-6})
seconds=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
out=chiprun_out/sets
mkdir -p $out
for set in A B; do
  for seed in $seeds; do
    python3 benchmarks/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 2>$out/${cell}_${set}_${seed}_t0.err | tail -1 > $out/${cell}_${set}_${seed}_t0.json
    mv chiprun_out/bench_${cell}_s${seed}_t0.json $out/${cell}_${set}_${seed}_rec.json
    echo "$cell $set $seed $(cut -c1-330 $out/${cell}_${set}_${seed}_t0.json)"
  done
  [ "${3:-1}" = 0 ] && continue
  python3 benchmarks/run.py --workload $cell --seed 555550002 --seconds $seconds --trace 1 2>$out/${cell}_${set}_trace.err | tail -1 > $out/${cell}_${set}_trace.json
  echo "$cell $set trace $(cut -c1-1200 $out/${cell}_${set}_trace.json)"
done
grep -h "reference check\|client:\|FAILED" $out/${cell}_*.err | cut -c1-200
