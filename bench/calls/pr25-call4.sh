# PR 25, chip call 4, as run: chiprun --chips 1 -- bash bench/scripts/call4.sh (the directory was bench/scripts/ then;
# bench/scripts/q6_diag.py is bench/diag/q6_fault.py now). Kept so that what produced each number can be re-read.
set -x
python3 bench/control.py --workload tpcds-sf1-store.q3-q55 --seeds 24 --control-seeds 3 --seconds 1 --first-seed 2600000001 2>chiprun_out/control_tpcds.err | tee chiprun_out/control_tpcds.jsonl | tail -1
python3 bench/measure.py --out chiprun_out/sets_tpcds.jsonl --workload tpcds-sf1-store.q3-q55 --seconds 51 --sets 2 --runs 6
python3 bench/control.py --workload tpch-sf1.q1 --seeds 12 --control-seeds 3 --seconds 1 --first-seed 2700000001 2>chiprun_out/control_q1.err | tee chiprun_out/control_q1.jsonl | tail -1
