# PR 25, chip call 2, as run: chiprun --chips 1 -- bash bench/scripts/call2.sh (the directory was bench/scripts/ then;
# bench/scripts/q6_diag.py is bench/diag/q6_fault.py now). Kept so that what produced each number can be re-read.
set -x
M="python3 bench/measure.py --out chiprun_out/try2.jsonl"
$M --workload tpcds-sf1-store.q3-q55 --seconds 10 --sets 1 --runs 2 --first-seed 2400000011
$M --workload tpcds-sf1-store.q3-q55 --seconds 10 --sets 1 --runs 1 --trace 1 --first-seed 7
python3 bench/control.py --workload tpch-sf1.q6 --seeds 12 --control-seeds 3 --seconds 1 2>chiprun_out/control_q6.err | tee chiprun_out/control_q6.jsonl | tail -1
python3 bench/measure.py --out chiprun_out/sets_q1.jsonl --workload tpch-sf1.q1 --seconds 51 --sets 2 --runs 6
python3 bench/control.py --workload rowconv-212x1m.to-rows --seeds 3 --control-seeds 3 --seconds 1 2>chiprun_out/control_rowconv.err | tee chiprun_out/control_rowconv.jsonl | tail -1
