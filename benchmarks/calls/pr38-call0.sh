#!/bin/sh
# PR 38, call 0 (ISSUE 38 step 0): the PARENT tree with only this PR's
# benchmark files laid over it (.bench_checkout/parent), one chip: one cold
# run, one warm run and one traced run of rowconv-212x1m-from.from-rows.
# The worker's compiles are named on stderr (JAX_LOG_COMPILES).
cd .bench_checkout/parent || exit 2
out=../../chiprun_out/pr38
mkdir -p "$out"
run() {  # name trace seed [extra args]
  name=$1 trace=$2 seed=$3
  shift 3
  t0=$(date +%s)
  timeout 1200 env JAX_LOG_COMPILES=1 python3 bench/run.py --workload rowconv-212x1m-from.from-rows \
    --seed "$seed" --seconds 51 --trace "$trace" "$@" > "$out/call0-$name.out" 2> "$out/call0-$name.err"
  rc=$?
  echo "== $name rc=$rc wall=$(( $(date +%s) - t0 ))s"
  grep -E '^setup ' "$out/call0-$name.out"
  tail -n 1 "$out/call0-$name.out" | cut -c1-3000
  grep -E '^check |fact worker' "$out/call0-$name.err" | cut -c1-600
}
run cold 0 3800000011
run warm 0 3800104741
run traced 1 3800209471 --save-trace "$out/call0-traced.trace.json"
