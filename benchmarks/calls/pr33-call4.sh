# PR 33, chip call 4 (four chips): chiprun --chips 4 --timeout 690 -- bash benchmarks/calls/pr33-call4.sh
# What call 3 had no time left for: the traced run of the change on tpcds-sf10-web.q95-x4 (PERF.md 5's span and
# device-program tables), then a second pair if it can end (the four-chip programs are in the cache since call 3).
PR_TAG=pr33; CALL=call4; CHANGE_DIR=$PWD/.smoke_checkout  # `git archive $(git write-tree)` of the final tree
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( 640 - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup\|^fact" "$OUT/$1.out" "$OUT/$1.err" | cut -c1-400 | tail -4; }
CELL=tpcds-sf10-web.q95-x4
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
KEEP_TRACE=1 bench_run change $CELL 3300733081 1; facts change-$CELL-3300733081-t1
T="$OUT/change-$CELL-3300733081-t1.trace.json"
if [ -f "$T" ]; then
  python3 benchmarks/calls/pr29_trace.py "$T" jit__ >"$OUT/trace-programs-3300733081.txt" 2>&1; head -44 "$OUT/trace-programs-3300733081.txt" | cut -c1-200
  python3 benchmarks/calls/pr32_spans.py "$T" | cut -c1-200 | head -40; rm -f "$T"
fi
if [ "$(left)" -gt 330 ]; then bench_run change $CELL 3300837803 0; facts change-$CELL-3300837803-t0; fi
if [ "$(left)" -gt 190 ]; then bench_run parent $CELL 3300837803 0; facts parent-$CELL-3300837803-t0; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call4.jsonl" | cut -c1-400 | head -60
python3 benchmarks/calls/pr31_spans.py "$OUT/runs-call4.jsonl" | cut -c1-260 | head -60
