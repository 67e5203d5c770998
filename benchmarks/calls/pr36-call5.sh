# PR 36, chip call 5 (one chip), the final tree: chiprun --chips 1 --timeout 2400 -- env CHANGE_DIR=$PWD/.smoke_checkout bash benchmarks/calls/pr36-call5.sh
# The change from a `git archive $(git write-tree)`: the committed files are enough. One untraced pair of q1 at 51 s
# (call 2's pair ran before `device.wait(sort_order)` was added), one traced run of the store star (every metric of
# the cell, with the matcher's slack), then one more untraced pair of the string transcode at ONE path (call 3's
# single pair read +1.2% on a run that had just compiled for 268 s), the change first this time.
PR_TAG=pr36; CALL=${CALL:-call5}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-2200} - ( $(date +%s) - t0 ) )); }
Q1=tpch-sf1.q1; S=tpcds-sf1-store.q3-q55; RS=rowconv-155x1m-strings.to-rows
A=${SEED_A:-3601151923}; TS=${SEED_TS:-3601256671}; B=${SEED_B:-3601361407}
xla() { grep -h "fact" "$OUT/$1.err" | grep -o "'xla[^,}]*" | tr '\n' ' '; echo; grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run change $Q1 $A 0
bench_run parent $Q1 $A 0
if [ "$(left)" -gt 900 ]; then
  KEEP_TRACE=1 bench_run change $S $TS 1
  TR="$OUT/change-$S-$TS-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/change-$S-$TS-t1.attribution.txt" 2>&1
    head -30 "$OUT/change-$S-$TS-t1.attribution.txt" | cut -c1-200; rm -f "$TR"
  fi
fi
# the string transcode, both sides at the parent's path (the change's program laid over it for its run)
if [ "$(left)" -gt 800 ]; then
  mv .bench_checkout/spark_rapids_jni_tpu .bench_checkout/.parent_program
  cp -r "$CHANGE_DIR/spark_rapids_jni_tpu" .bench_checkout/spark_rapids_jni_tpu
  CHANGE_DIR=$HERE/.bench_checkout bench_run change $RS $B 0; xla change-$RS-$B-t0
  rm -rf .bench_checkout/spark_rapids_jni_tpu; mv .bench_checkout/.parent_program .bench_checkout/spark_rapids_jni_tpu
  if [ "$(left)" -gt 300 ]; then bench_run parent $RS $B 0; xla parent-$RS-$B-t0; fi
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -40
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
