# PR 28, chip call 1: chiprun --chips 4 --timeout 1900 -- bash benchmarks/calls/pr28-call1.sh
# (1) the PARENT commit (.bench_checkout/, `git archive ab1bb1c`) on the new cell: with the new files absent, and with
#     this PR's benchmark files laid over it as the driver does; both must exit at once, not hang.
# (2) the change, nothing cached: one untraced run of the new cell at 51 s (cold set-up, every program that compiles is
#     in the xla.compile lines of stderr), then one traced run (warm) that saves its reduced trace.
set -x
mkdir -p chiprun_out
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"; ls "${JAX_COMPILATION_CACHE_DIR:-.jax_cache}" 2>/dev/null | wc -l
( cd .bench_checkout && time timeout 300 python3 bench/run.py --workload tpcds-sf10-web.q95-x4 --seed 2400000017 --seconds 51 --trace 0; echo "parent, new files absent: rc=$?" )
mkdir -p .bench_work && rm -rf .bench_work/parent_overlay && cp -r .bench_checkout .bench_work/parent_overlay \
  && cp BENCHMARK.json .bench_work/parent_overlay/ && cp -r bench/. .bench_work/parent_overlay/bench/
( cd .bench_work/parent_overlay && time timeout 300 python3 bench/run.py --workload tpcds-sf10-web.q95-x4 --seed 2400000017 --seconds 51 --trace 0; echo "parent under this PR's benchmark files: rc=$?" )
rm -rf .bench_work/parent_overlay
time timeout 1200 python3 bench/run.py --workload tpcds-sf10-web.q95-x4 --seed 2400000017 --seconds 51 --trace 0 2>chiprun_out/pr28_cold.err | tee chiprun_out/pr28_cold.out | tail -30
grep -c "xla.compile\|Compiling" chiprun_out/pr28_cold.err; tail -25 chiprun_out/pr28_cold.err
time timeout 420 python3 bench/run.py --workload tpcds-sf10-web.q95-x4 --seed 2400104746 --seconds 51 --trace 1 --save-trace chiprun_out/pr28_q95_trace.json 2>chiprun_out/pr28_traced.err | tee chiprun_out/pr28_traced.out | tail -12
tail -30 chiprun_out/pr28_traced.err
ls "${JAX_COMPILATION_CACHE_DIR:-.jax_cache}" 2>/dev/null | wc -l
