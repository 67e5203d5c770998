#!/bin/sh
# PR 38, call 1 (ISSUE 38 step 5): the CHANGE (this checkout), one chip, the
# new cell rowconv-212x1m-from.from-rows: one traced run (trace saved), a
# second seed untraced with the worker's compiles named (JAX_LOG_COMPILES),
# the control against the program (bench/control.py), then two sets of six
# seeds at 51 s (bench/measure.py).
out=chiprun_out/pr38
mkdir -p "$out"
W=rowconv-212x1m-from.from-rows
run() {  # name trace seed [extra args]
  name=$1 trace=$2 seed=$3
  shift 3
  t0=$(date +%s)
  timeout 900 env JAX_LOG_COMPILES=1 python3 bench/run.py --workload $W \
    --seed "$seed" --seconds 51 --trace "$trace" "$@" > "$out/call1-$name.out" 2> "$out/call1-$name.err"
  rc=$?
  echo "== $name rc=$rc wall=$(( $(date +%s) - t0 ))s"
  grep -E '^setup ' "$out/call1-$name.out"
  tail -n 1 "$out/call1-$name.out" | cut -c1-3500
  grep -E 'fact worker' "$out/call1-$name.err" | cut -c1-900
}
run traced 1 3800314159 --save-trace "$out/call1-traced.trace.json"
run seed2 0 3800418881
echo "== control"
timeout 600 python3 bench/control.py --workload $W --seeds 1 --control-seeds 1 --seconds 2 \
  --first-seed 3800523457 2> "$out/call1-control.err" | tail -n 2
echo "== measure"
timeout 2400 python3 bench/measure.py --workload $W --seconds 51 --sets 2 --runs 6 \
  --first-seed 3800600029 --out "$out/call1-measure.jsonl"
