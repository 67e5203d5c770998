# PR 37, chip call 2 (one chip): chiprun --chips 1 --timeout 3000 -- bash benchmarks/calls/pr37-call2.sh
# tpcds-sf1-store.q3-q55, unclaimed and expected not to move: both its group-bys carry the STRING key i_brand, which
# the dense form's dtype gate refuses before any launch (groupby.sorted only; device_programs_per_request and
# host_wait_ms hold). One traced run of the change (every per-layer metric of the cell), then a pair at 51 s: parent
# (.bench_checkout/: `git archive 19f68cd`) and change; last, if time is left, tpch-sf1.q1 once more from
# CHANGE_DIR (.smoke_checkout/: a `git archive $(git write-tree)`: the committed files are enough).
PR_TAG=pr37; CALL=${CALL:-call2}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-2800} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
S=tpcds-sf1-store.q3-q55; Q1=tpch-sf1.q1
ST=${SEED_S:-3700628373}; TS=${SEED_TS:-3700733081}; QA=${SEED_QA:-3700837803}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent $S $ST 0; facts parent-$S-$ST-t0
if [ "$(left)" -gt 300 ]; then bench_run change $S $ST 0; facts change-$S-$ST-t0; fi
if [ "$(left)" -gt 300 ]; then
  KEEP_TRACE=1 bench_run change $S $TS 1
  TR="$OUT/change-$S-$TS-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/change-$S-$TS-t1.attribution.txt" 2>&1
    head -40 "$OUT/change-$S-$TS-t1.attribution.txt" | cut -c1-200; rm -f "$TR"
  fi
fi
if [ "$(left)" -gt 400 ]; then
  bench_run change $Q1 $QA 0; facts change-$Q1-$QA-t0
  if [ "$(left)" -gt 200 ]; then bench_run parent $Q1 $QA 0; facts parent-$Q1-$QA-t0; fi
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -60
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
