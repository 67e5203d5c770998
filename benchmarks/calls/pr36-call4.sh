# PR 36, chip call 4 (FOUR chips): chiprun --chips 4 --timeout 2400 -- bash benchmarks/calls/pr36-call4.sh
# tpcds-sf10-web.q95-x4: one traced run of the change (CHANGE_DIR: a `git archive $(git write-tree)`), read through
# the new metrics and benchmarks/calls/pr36_attribution.py (all four chips' programs against the launches).
PR_TAG=pr36; CALL=${CALL:-call4}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
X=tpcds-sf10-web.q95-x4; T=${SEED_T:-3600942561}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
KEEP_TRACE=1 bench_run change $X $T 1
grep -h "^setup" "$OUT/change-$X-$T-t1.out" | cut -c1-300 | tail -1
grep -h "fact" "$OUT/change-$X-$T-t1.err" | cut -c1-400 | tail -2
TR="$OUT/change-$X-$T-t1.trace.json"
if [ -f "$TR" ]; then
  python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/change-$X-$T-t1.attribution.txt" 2>&1
  head -100 "$OUT/change-$X-$T-t1.attribution.txt" | cut -c1-200
  python3 benchmarks/calls/pr29_trace.py "$TR" groupby_program >"$OUT/change-$X-$T-t1.programs.txt" 2>&1
  gzip -f "$TR"
fi
grep -h "^request" "$OUT/change-$X-$T-t1.out" | awk '{print $8}' | sort -n | awk '{a[NR]=$1} END {print "traced latency median ms", a[int((NR+1)/2)], "of", NR}'
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
