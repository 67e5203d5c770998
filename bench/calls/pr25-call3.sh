# PR 25, chip call 3, as run: chiprun --chips 1 -- bash bench/scripts/call3.sh (the directory was bench/scripts/ then;
# bench/scripts/q6_diag.py is bench/diag/q6_fault.py now). Kept so that what produced each number can be re-read.
set -x
python3 bench/scripts/q6_diag.py 2200007920 6 bisect 2>/dev/null | tee chiprun_out/q6_diag.txt
python3 bench/scripts/q6_diag.py 2200007920 3 2>/dev/null | tee -a chiprun_out/q6_diag.txt
python3 bench/control.py --workload tpch-sf1.q6 --seeds 30 --control-seeds 0 --seconds 0.4 --first-seed 2500000001 2>/dev/null | tee chiprun_out/control_q6_b.jsonl | cut -c1-200
