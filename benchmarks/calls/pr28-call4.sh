# PR 28, chip call 4: chiprun --chips 1 --timeout 420 -- bash benchmarks/calls/pr28-call4.sh
# An old cell traced under the benchmark as this PR leaves it, on both sides, as the driver runs it: the parent with
# BENCHMARK.json and bench/ laid over it, then the change. The per-layer lines of tpch-sf1.q1 must be whole on both.
set -x
mkdir -p chiprun_out .bench_work && rm -rf .bench_work/parent_overlay && cp -r .bench_checkout .bench_work/parent_overlay \
  && cp BENCHMARK.json .bench_work/parent_overlay/ && cp -r bench/. .bench_work/parent_overlay/bench/
( cd .bench_work/parent_overlay && python3 bench/run.py --workload tpch-sf1.q1 --seed 2700000031 --seconds 51 --trace 1 2>/dev/null | tail -1 | cut -c1-1500 )
rm -rf .bench_work/parent_overlay
python3 bench/run.py --workload tpch-sf1.q1 --seed 2700000031 --seconds 51 --trace 1 2>/dev/null | tail -1 | cut -c1-1500
