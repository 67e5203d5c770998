# PR 25, chip call 5, as run: chiprun --chips 1 -- bash bench/scripts/call5.sh (the directory was bench/scripts/ then;
# bench/scripts/q6_diag.py is bench/diag/q6_fault.py now). Kept so that what produced each number can be re-read.
set -x
python3 bench/measure.py --out chiprun_out/sets_rowconv.jsonl --workload rowconv-212x1m.to-rows --seconds 51 --sets 2 --runs 6
for w in tpcds-sf1-store.q3-q55 tpch-sf1.q1 rowconv-212x1m.to-rows; do
python3 bench/measure.py --out chiprun_out/traces.jsonl --workload $w --seconds 51 --sets 1 --runs 3 --trace 1 --first-seed 2800000033
done
