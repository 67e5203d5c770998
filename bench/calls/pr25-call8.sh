# PR 25 (answering the review), chip call 8: chiprun --chips 1 --timeout 3300 -- bash bench/calls/pr25-call8.sh
# The TPC-DS star at the spec's shapes: the string fault on the chip, and the star with i_brand left out.
# rowconv again, its region leased for the larger of request and reply (call 7 streamed the 1.2 GB reply over the socket).
set -x
python3 bench/diag/tpcds_string_fault.py 3400000007 2>chiprun_out/tpcds_diag.err | tee chiprun_out/tpcds_diag.txt | cut -c1-600
python3 bench/measure.py --out chiprun_out/sets_rowconv3.jsonl --workload rowconv-212x1m.to-rows --seconds 51 --sets 2 --runs 6 --first-seed 3500000017
python3 bench/measure.py --out chiprun_out/traces3.jsonl --workload rowconv-212x1m.to-rows --seconds 51 --sets 1 --runs 3 --trace 1 --first-seed 3600000031 --save-trace chiprun_out/rowconv_trace3.json
python3 bench/control.py --workload rowconv-212x1m.to-rows --seeds 4 --control-seeds 3 --seconds 1 --first-seed 3700000003 2>chiprun_out/control_rowconv3.err | tee chiprun_out/control_rowconv3.jsonl | tail -1
ps aux | grep "[s]park_rapids_jni_tpu.sidecar" | wc -l
