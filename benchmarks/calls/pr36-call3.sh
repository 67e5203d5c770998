# PR 36, chip call 3 (one chip): chiprun --chips 1 --timeout 3300 -- bash benchmarks/calls/pr36-call3.sh
# One more traced q1 of the change, then rowconv-155x1m-strings.to-rows. The encode's Mosaic program misses the persistent cache from a checkout at another
# path (PERF.md 7), so both sides run from ONE path: the parent in .bench_checkout/ twice (the second set-up is the
# warm one), then the change's spark_rapids_jni_tpu/ laid over it (bench/ is the same on both sides) and run twice,
# the second time traced. The worker's xla.* counters ride in each run's `fact` line on stderr: a program that the
# change renamed or keyed anew would show as a cache miss in the change's first set-up.
PR_TAG=pr36; CALL=${CALL:-call3}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-3100} - ( $(date +%s) - t0 ) )); }
RS=rowconv-155x1m-strings.to-rows
A=${SEED_A:-3600733081}; B=${SEED_B:-3600837803}; T=${SEED_T:-3600314159}
xla() { grep -h "fact" "$OUT/$1.err" | grep -o "'xla[^,}]*" | tr '\n' ' '; echo; grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
# first, q1 traced once more: call 2 read `host_wait_ms` 83.6% of the busy time (197 ms of `groupby.segments` were a
# stall in dispatch with no `device.wait`) and lost one `_body` to the window's edge; both mended since
Q1=tpch-sf1.q1; TQ=${SEED_TQ:-3601047193}
KEEP_TRACE=1 bench_run change $Q1 $TQ 1
TR="$OUT/change-$Q1-$TQ-t1.trace.json"
if [ -f "$TR" ]; then
  python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/change-$Q1-$TQ-t1.attribution.txt" 2>&1
  head -50 "$OUT/change-$Q1-$TQ-t1.attribution.txt" | cut -c1-200; gzip -f "$TR"
fi
bench_run parent $RS $A 0; xla parent-$RS-$A-t0
if [ "$(left)" -gt 400 ]; then bench_run parent $RS $B 0; xla parent-$RS-$B-t0; fi
# the change at the parent's path
mv .bench_checkout/spark_rapids_jni_tpu .bench_checkout/.parent_program
cp -r "$CHANGE_DIR/spark_rapids_jni_tpu" .bench_checkout/spark_rapids_jni_tpu
CHANGE_DIR=$HERE/.bench_checkout
if [ "$(left)" -gt 700 ]; then bench_run change $RS $B 0; xla change-$RS-$B-t0; fi
if [ "$(left)" -gt 300 ]; then
  KEEP_TRACE=1 bench_run change $RS $T 1; xla change-$RS-$T-t1
  TR="$OUT/change-$RS-$T-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/change-$RS-$T-t1.attribution.txt" 2>&1
    head -60 "$OUT/change-$RS-$T-t1.attribution.txt" | cut -c1-200
    python3 benchmarks/calls/pr29_trace.py "$TR" _jit_encode >"$OUT/change-$RS-$T-t1.programs.txt" 2>&1
    gzip -f "$TR"
  fi
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -40
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
