# PR 33, chip call 3 (four chips): chiprun --chips 4 --timeout 1450 -- bash benchmarks/calls/pr33-call3.sh
# First the lanes on the mesh (pr33_bits.py --chips 4: ws_wh's wh_lo / wh_hi and the mesh Filter's present from the
# stage's one program over the row-sharded arrays against the eager evaluator on the first chip and against numpy),
# then tpcds-sf10-web.q95-x4, the claimed four-chip cell: the change first (its first run compiles the new programs),
# the parent (.bench_checkout/), a traced run of the change, and a second pair if the call's time allows. A run's
# set-up line says what it compiled; every run starts only if it can end.
PR_TAG=pr33; CALL=call3; CHANGE_DIR=$PWD/.smoke_checkout  # `git archive $(git write-tree)` of the final tree: the committed files are enough
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( 1350 - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup\|^fact" "$OUT/$1.out" "$OUT/$1.err" | cut -c1-400 | tail -4; }
CELL=tpcds-sf10-web.q95-x4
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
(cd "$CHANGE_DIR" && timeout -k 10 420 python3 benchmarks/calls/pr33_bits.py --chips 4 --seeds 3300000007 --out "$OUT/bits-4chip.jsonl") 2>"$OUT/bits-4chip.err" | cut -c1-700
echo "bits rc ${PIPESTATUS[0]}"; grep -v cpu_aot_loader "$OUT/bits-4chip.err" | tail -5 | cut -c1-300
bench_run change $CELL 3300628357 0; facts change-$CELL-3300628357-t0
if [ "$(left)" -gt 400 ]; then bench_run parent $CELL 3300628357 0; facts parent-$CELL-3300628357-t0; fi
if [ "$(left)" -gt 330 ]; then
  KEEP_TRACE=1 bench_run change $CELL 3300733081 1; facts change-$CELL-3300733081-t1
  T="$OUT/change-$CELL-3300733081-t1.trace.json"
  if [ -f "$T" ]; then
    python3 benchmarks/calls/pr29_trace.py "$T" jit__ >"$OUT/trace-programs-3300733081.txt" 2>&1; head -50 "$OUT/trace-programs-3300733081.txt"
    python3 benchmarks/calls/pr32_spans.py "$T" | cut -c1-260; rm -f "$T"
  fi
fi
if [ "$(left)" -gt 330 ]; then
  bench_run parent $CELL 3300837803 0; facts parent-$CELL-3300837803-t0
  bench_run change $CELL 3300837803 0; facts change-$CELL-3300837803-t0
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call3.jsonl" | cut -c1-300 | head -60
python3 benchmarks/calls/pr31_spans.py "$OUT/runs-call3.jsonl" | cut -c1-260 | head -40
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
