# PR 25 (answering the review), chip call 7: chiprun --chips 1 --timeout 3300 -- bash bench/calls/pr25-call7.sh
# q6's second faulty seed in two more processes; rowconv at the source's type order (1,160-byte rows),
# wire bytes built in set-up: two sets of 6 at 51 s, 3 traced runs, the control.
set -x
python3 bench/diag/q6_fault.py 2500142543 3 2>/dev/null | tee chiprun_out/q6_diag_seed2.txt
python3 bench/diag/q6_fault.py 2500142543 3 2>/dev/null | tee -a chiprun_out/q6_diag_seed2.txt
python3 bench/measure.py --out chiprun_out/sets_rowconv2.jsonl --workload rowconv-212x1m.to-rows --seconds 51 --sets 2 --runs 6 --first-seed 3100000019
python3 bench/measure.py --out chiprun_out/traces2.jsonl --workload rowconv-212x1m.to-rows --seconds 51 --sets 1 --runs 3 --trace 1 --first-seed 3200000033 --save-trace chiprun_out/rowconv_trace2.json
python3 bench/control.py --workload rowconv-212x1m.to-rows --seeds 4 --control-seeds 3 --seconds 1 --first-seed 3300000001 2>chiprun_out/control_rowconv2.err | tee chiprun_out/control_rowconv2.jsonl | tail -1
ps aux | grep "[s]park_rapids_jni_tpu.sidecar" | wc -l
