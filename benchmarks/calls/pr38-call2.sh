#!/bin/sh
# PR 38, call 2: one traced run of the final change (the put's wait under
# sidecar.worker.decode_table), then one pair parent -> change of the cell
# that shares the worker and its loop (rowconv-212x1m.to-rows) and one of
# the new cell, each pair on one seed. The parent is .bench_checkout/parent
# (dc8599b with this PR's benchmark files: the old cells' files unchanged).
# tpch-sf1.q1 runs none of the changed code and is left to the driver.
out=$(pwd)/chiprun_out/pr38
mkdir -p "$out"
run() {  # dir name workload trace seed [extra args]
  dir=$1 name=$2 w=$3 trace=$4 seed=$5
  shift 5
  t0=$(date +%s)
  (cd "$dir" && timeout 900 python3 bench/run.py --workload "$w" --seed "$seed" --seconds 51 --trace "$trace" "$@" \
    > "$out/call2-$name.out" 2> "$out/call2-$name.err")
  rc=$?
  echo "== $name rc=$rc wall=$(( $(date +%s) - t0 ))s"
  grep -E '^setup ' "$out/call2-$name.out"
  tail -n 1 "$out/call2-$name.out" | cut -c1-3500
  grep -E 'fact worker' "$out/call2-$name.err" | cut -c1-600
}
P=.bench_checkout/parent
run . from-traced rowconv-212x1m-from.from-rows 1 3800727211 --save-trace "$out/call2-from-traced.trace.json"
run $P torows-parent rowconv-212x1m.to-rows 0 3800831143
run . torows-change rowconv-212x1m.to-rows 0 3800831143
run $P from-parent rowconv-212x1m-from.from-rows 0 3800935071
run . from-change rowconv-212x1m-from.from-rows 0 3800935071
