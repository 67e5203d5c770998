# PR 28, chip call 6 (review round): chiprun --chips 1 --timeout 520 -- bash benchmarks/calls/pr28-call6.sh
# rowconv-212x1m.to-rows never enters plan/ or parallel/; REVIEW.md asks for it once per side all the same: the parent
# (.bench_checkout/, `git archive ab1bb1c`) and the change on one chip, one seed a pair, order P C (C P too if the first
# pair took under 4 minutes).
set -x
mkdir -p chiprun_out
t0=$(date +%s)
run() { ( cd "$1" && python3 bench/run.py --workload rowconv-212x1m.to-rows --seed "$2" --seconds 51 --trace 0 2>/dev/null | tail -1 | cut -c1-420 ); }
{
echo PARENT; run .bench_checkout 2900000039
echo CHANGE; run . 2900000039
if [ $(( $(date +%s) - t0 )) -lt 240 ]; then
  echo CHANGE; run . 2900104768
  echo PARENT; run .bench_checkout 2900104768
fi
} | tee chiprun_out/pr28_rowconv_pairs.out
