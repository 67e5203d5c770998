# PR 28, chip call 3: chiprun --chips 1 --timeout 1100 -- bash benchmarks/calls/pr28-call3.sh
# tpch-sf1.q1 shares plan.compile_ir and _Exec with the new cell: the parent (.bench_checkout/, `git archive ab1bb1c`)
# and the change on one chip, one seed a pair, order P C (C P too if the first pair took under 7 minutes: warm cache).
set -x
mkdir -p chiprun_out
t0=$(date +%s)
run() { ( cd "$1" && python3 bench/run.py --workload tpch-sf1.q1 --seed "$2" --seconds 51 --trace 0 2>/dev/null | tail -1 | cut -c1-420 ); }
echo PARENT; run .bench_checkout 2600000027
echo CHANGE; run . 2600000027
if [ $(( $(date +%s) - t0 )) -lt 420 ]; then
  echo CHANGE; run . 2600104756
  echo PARENT; run .bench_checkout 2600104756
fi
