# PR 25, chip call 1, as run: chiprun --chips 1 -- bash bench/scripts/call1.sh (the directory was bench/scripts/ then;
# bench/scripts/q6_diag.py is bench/diag/q6_fault.py now). Kept so that what produced each number can be re-read.
set -x
env | grep -i "jax\|tpu\|xla" ; nproc; df -h /root/repo | tail -1
M="python3 bench/measure.py --out chiprun_out/try1.jsonl"
$M --workload tpch-sf1.q6 --seconds 10 --sets 1 --runs 2
$M --workload tpch-sf1.q6 --seconds 2 --sets 1 --runs 1 --trace 1 --first-seed 5 --save-trace chiprun_out/q6_trace.json
$M --workload tpch-sf1.q6 --seconds 10 --sets 1 --runs 1 --trace 1 --first-seed 6
$M --workload tpcds-sf1-store.q3-q55 --seconds 10 --sets 1 --runs 2
$M --workload tpcds-sf1-store.q3-q55 --seconds 10 --sets 1 --runs 1 --trace 1 --first-seed 6
$M --workload rowconv-212x1m.to-rows --seconds 10 --sets 1 --runs 1
$M --workload rowconv-212x1m.to-rows --seconds 10 --sets 1 --runs 1 --trace 1 --first-seed 6 --save-trace chiprun_out/rowconv_trace.json
$M --workload tpch-sf1.q1 --seconds 10 --sets 1 --runs 2
$M --workload tpch-sf1.q1 --seconds 10 --sets 1 --runs 1 --trace 1 --first-seed 6
ls -la chiprun_out; du -sh .jax_cache 2>/dev/null; ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l
