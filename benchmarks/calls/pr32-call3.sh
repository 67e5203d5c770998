# PR 32, chip call 3: chiprun --chips 1 --timeout 1900 -- bash benchmarks/calls/pr32-call3.sh
# After call 1 showed that a 27-lane lexsort does not compile in ten minutes (the 200-byte keys of pr32_lanes.py ran
# into the call's limit) ops/sort.py sorts a wide key chunk by chunk (_LEXSORT_LANES): the 200-byte shape of
# pr32_lanes.py on the chip under a time limit of its own, then bench/measure.py's second set of six on the store cell.
# The change is .smoke_checkout/ (`git archive $(git write-tree)` of the final tree).
PR_TAG=pr32; CALL=call3; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( 1850 - ( $(date +%s) - t0 ) )); }
S=tpcds-sf1-store.q3-q55
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
(cd "$CHANGE_DIR" && timeout -k 10 540 python3 benchmarks/calls/pr32_lanes.py --rows 50000 --seeds 2 --only desc) >"$OUT/lanes-desc.out" 2>"$OUT/lanes-desc.err"
echo "lanes desc rc $? after $(( $(date +%s) - t0 )) s"; tail -6 "$OUT/lanes-desc.out"
if [ "$(left)" -gt 1000 ]; then
  (cd "$CHANGE_DIR" && python3 bench/measure.py --workload $S --seconds 51 --sets 1 --runs 6 --out "$OUT/measure-$S-set2.jsonl") \
    >"$OUT/measure-$S-set2.out" 2>&1
  echo "measure set 2 rc $?"; tail -12 "$OUT/measure-$S-set2.out" | cut -c1-400
fi
# q1's programs a request, traced (its int8 keys take the lanes they took: 2,707 at the parent, ledger, PR 29)
if [ "$(left)" -gt 420 ]; then bench_run change tpch-sf1.q1 3201152023 1; fi
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
