# PR 32, chip call 0 (ISSUE 32 step 0): chiprun --chips 1 --timeout 3300 -- bash benchmarks/calls/pr32-call0.sh
# The PARENT (.bench_checkout/: `git archive e819cbe` with this PR's BENCHMARK.json and bench/ laid over it) on both new
# cells: a first run (cold where the machine's cache holds nothing), a warm run, a traced run. The store cell first:
# it is known to end; q95 on one chip is not, so each of its runs is under a time limit of its own. What time is left goes
# to the change on the same seeds (run0 change: this tree).
PR_TAG=pr32; CALL=call0
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( 3300 - ( $(date +%s) - t0 ) )); }
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
# bench_run with the compiles counted and a time limit: side cell seed trace limit
run0() {
  side=$1; cell=$2; seed=$3; trace=$4; limit=$5
  tag=$side-$cell-$seed-t$trace
  extra=""; if [ "$trace" = 1 ]; then extra="--save-trace $OUT/$tag.trace.json"; fi
  s0=$(date +%s)
  (cd "$(side_dir $side)" && timeout -k 10 $limit python3 "$HERE/benchmarks/calls/pr32_run.py" --workload $cell --seed $seed --seconds 51 --trace $trace $extra) \
    >"$OUT/$tag.out" 2>"$OUT/$tag.err"
  rc=$?
  echo "run $tag rc $rc wall_s $(( $(date +%s) - s0 ))"
  grep -h "^setup " "$OUT/$tag.out" | cut -c1-400
  grep -h "^request " "$OUT/$tag.out" | head -40 | cut -c1-160
  grep -h "pr32\] xla" "$OUT/$tag.err"
  grep -h "backend compile" "$OUT/$tag.err" | sort -t' ' -k4 -n -r | head -12
  grep -c "backend compile" "$OUT/$tag.err"
  tail -1 "$OUT/$tag.out" | cut -c1-3000
  if [ "$rc" != 0 ]; then tail -30 "$OUT/$tag.err"; fi
  if [ "$trace" = 1 ] && [ -f "$OUT/$tag.trace.json" ]; then
    python3 benchmarks/calls/pr29_trace.py "$OUT/$tag.trace.json" >"$OUT/$tag.programs.txt" 2>&1
    python3 benchmarks/calls/pr32_spans.py "$OUT/$tag.trace.json" | cut -c1-200
    rm -f "$OUT/$tag.trace.json"
  fi
  return $rc
}
S=tpcds-sf1-store.q3-q55; Q=tpcds-sf10-web.q95
run0 parent $S 3200000033 0 1800
run0 parent $S 3200104759 0 600
run0 parent $S 3200209489 1 600
lim=$(( $(left) - 420 )); [ $lim -gt 1500 ] && lim=1500
if [ $lim -gt 300 ] && run0 parent $Q 3200314201 0 $lim; then
  [ "$(left)" -gt 400 ] && run0 parent $Q 3200418923 0 380
  [ "$(left)" -gt 300 ] && run0 parent $Q 3200523647 1 280
fi
# with the time that is left, on the same machine and cache: the change's string lanes against numpy on the chip
# (PERF.md 7 (iv)), then the CHANGE (this tree) on the parent's seeds of the store cell, then of q95
if [ "$(left)" -gt 240 ]; then
  timeout -k 10 200 python3 benchmarks/calls/pr32_lanes.py >"$OUT/lanes.out" 2>"$OUT/lanes.err"; echo "lanes rc $?"; tail -8 "$OUT/lanes.out"
fi
[ "$(left)" -gt 260 ] && run0 change $S 3200104759 0 250
[ "$(left)" -gt 260 ] && run0 change $S 3200209489 1 250
[ "$(left)" -gt 400 ] && [ -s "$OUT/parent-$Q-3200418923-t0.out" ] && run0 change $Q 3200418923 0 380
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
