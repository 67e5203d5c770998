# PR 32, chip call 1 (ISSUE 32 step 5): chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr32-call1.sh
# The CHANGE from .smoke_checkout/ (`git archive $(git write-tree)`: the committed files are enough) on the new cell
# tpcds-sf1-store.q3-q55: a traced run, the string lanes against numpy on the chip (PERF.md 7 (iv)), bench/control.py
# (12 seeds, 3 of them with the float32 control), bench/measure.py (two sets of six at 51 s, the second last and only
# if it can end), one more pair parent (.bench_checkout/: `git archive e819cbe` + this PR's BENCHMARK.json and bench/)
# against change, and one pair each of tpch-sf1.q1 and rowconv-212x1m.to-rows. Every step starts only if it can end.
PR_TAG=pr32; CALL=call1; CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( 3400 - ( $(date +%s) - t0 ) )); }
S=tpcds-sf1-store.q3-q55
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
KEEP_TRACE=1 bench_run change $S 3200628373 1
T="$OUT/change-$S-3200628373-t1.trace.json"
if [ -f "$T" ]; then
  python3 benchmarks/calls/pr29_trace.py "$T" >"$OUT/change-$S-3200628373-t1.programs.txt" 2>&1; head -24 "$OUT/change-$S-3200628373-t1.programs.txt"
  python3 benchmarks/calls/pr32_spans.py "$T" | cut -c1-260; rm -f "$T"
fi
grep -h "^setup " "$OUT/change-$S-3200628373-t1.out" | cut -c1-300
if [ "$(left)" -gt 900 ]; then
  (cd "$CHANGE_DIR" && timeout -k 10 600 python3 benchmarks/calls/pr32_lanes.py --rows 50000 --seeds 2) >"$OUT/lanes.out" 2>"$OUT/lanes.err"
  echo "lanes rc $?"; tail -8 "$OUT/lanes.out"
fi
if [ "$(left)" -gt 700 ]; then
  (cd "$CHANGE_DIR" && timeout -k 10 900 python3 bench/control.py --workload $S --seeds 12 --control-seeds 3 --seconds 1) \
    >"$OUT/control-$S.jsonl" 2>"$OUT/control-$S.err"
  echo "control rc $?"; tail -1 "$OUT/control-$S.jsonl" | cut -c1-900
fi
measure_set() {  # one set of six: every set uses measure.py's own seeds, so two calls are its two sets
  (cd "$CHANGE_DIR" && python3 bench/measure.py --workload $S --seconds 51 --sets 1 --runs 6 --out "$OUT/measure-$S-set$1.jsonl") \
    >"$OUT/measure-$S-set$1.out" 2>&1
  echo "measure set $1 rc $?"; tail -12 "$OUT/measure-$S-set$1.out" | cut -c1-400
}
[ "$(left)" -gt 1100 ] && measure_set 1
if [ "$(left)" -gt 260 ]; then bench_run change tpch-sf1.q1 3200733097 0; fi
if [ "$(left)" -gt 260 ]; then bench_run parent tpch-sf1.q1 3200733097 0; fi
if [ "$(left)" -gt 280 ]; then bench_run parent rowconv-212x1m.to-rows 3200837821 0; fi
if [ "$(left)" -gt 280 ]; then bench_run change rowconv-212x1m.to-rows 3200837821 0; fi
if [ "$(left)" -gt 400 ]; then bench_run parent $S 3200942561 0; bench_run change $S 3200942561 0; fi
[ "$(left)" -gt 1100 ] && measure_set 2
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call1.jsonl" | cut -c1-300 | head -60
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
