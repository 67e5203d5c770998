# PR 31, chip call 1: chiprun --chips 4 --timeout 2400 -- bash benchmarks/calls/pr31-call1.sh
# tpcds-sf10-web.q95-x4 on four chips: the parent (.bench_checkout/, `git archive ed90296`) and the change
# (.smoke_checkout/, `git archive $(git write-tree)` of the final tree: the committed files are enough), order
# P C [C P] [C traced] [C traced], a seed a pair and one a traced run, untraced at 51 s. The change's FIRST run
# compiles every program behind an exchange at its new shape; every further run starts only if it can end inside
# the call. A run's set-up line says what it compiled (xla_backend_compiles): a second seed of the change should
# compile nothing, which is what "every seed lands on one step" buys.
PR_TAG=pr31; CALL=call1; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( 2400 - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup\|^fact" "$OUT/$1.out" "$OUT/$1.err" | cut -c1-400 | tail -4; }
CELL=tpcds-sf10-web.q95-x4
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent $CELL 3100000019 0; facts parent-$CELL-3100000019-t0
bench_run change $CELL 3100000019 0; facts change-$CELL-3100000019-t0
if [ "$(left)" -gt 420 ]; then
  bench_run change $CELL 3100104743 0; facts change-$CELL-3100104743-t0
fi
if [ "$(left)" -gt 330 ]; then
  KEEP_TRACE=1 bench_run change $CELL 3100209469 1; facts change-$CELL-3100209469-t1
  python3 benchmarks/calls/pr29_trace.py "$OUT/change-$CELL-3100209469-t1.trace.json" exchange >"$OUT/trace-programs-3100209469.txt" 2>&1
  gzip -f "$OUT/change-$CELL-3100209469-t1.trace.json"; ls -l "$OUT"/*.gz
fi
if [ "$(left)" -gt 300 ]; then
  bench_run parent $CELL 3100104743 0; facts parent-$CELL-3100104743-t0
fi
if [ "$(left)" -gt 200 ]; then
  bench_run change $CELL 3100314197 1; facts change-$CELL-3100314197-t1
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call1.jsonl"
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
python3 benchmarks/calls/pr31_spans.py "$OUT/runs-call1.jsonl" | cut -c1-260
