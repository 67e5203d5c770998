# PR 28, chip call 5 (review round): chiprun --chips 4 --timeout 600 -- bash benchmarks/calls/pr28-call5.sh
# The new cell's SECOND set, untraced, 51 s: the first three seeds of call 2's set again, so that two sets of the
# same seeds can be laid side by side (40 chip-minutes this round: three runs of ~2.6 min on four chips, no more).
# The first run goes alone under a limit: if the compile cache that came with the machine no longer holds the
# cell's programs (set-up 77 s warm, 969 s cold), the call stops there instead of spending the budget on compiles.
set -x
mkdir -p chiprun_out
timeout 230 python3 bench/run.py --workload tpcds-sf10-web.q95-x4 --seed 2500000041 --seconds 51 --trace 0 \
  > chiprun_out/pr28_set2_run0.out 2> chiprun_out/pr28_set2_run0.err
rc=$?
tail -c 1500 chiprun_out/pr28_set2_run0.out
grep -c "backend compile" chiprun_out/pr28_set2_run0.err
grep "^setup\|^fact\|^check" chiprun_out/pr28_set2_run0.err chiprun_out/pr28_set2_run0.out | cut -c1-400
if [ $rc -ne 0 ]; then echo "first run rc=$rc: cold cache or a fault, stopping"; tail -20 chiprun_out/pr28_set2_run0.err; exit 9; fi
python3 bench/measure.py --workload tpcds-sf10-web.q95-x4 --seconds 51 --sets 1 --runs 2 --first-seed 2500104770 --out chiprun_out/pr28_set2.jsonl
