# PR 34, chip call 0 (ISSUE 34 step 0): chiprun --chips 1 --timeout 3000 -- bash benchmarks/calls/pr34-call0.sh
# The PARENT on the new cell: the tree as it stood before ops/row_conversion.py was touched (fc974bf with this PR's
# BENCHMARK.json and bench/ laid over it: at the time of the call the working tree WAS that), a first run (cold where the
# machine's cache holds nothing), a warm run, a traced run, each under a time limit of its own; then the two forms of the
# encode the parent holds, a process each, at the cell's axis (benchmarks/calls/pr34_forms.py).
PR_TAG=pr34; CALL=call0
. benchmarks/calls/pr26-common.sh
DIR=${PARENT_DIR:-$HERE}
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-3000} - ( $(date +%s) - t0 ) )); }
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
# one run with the worker's compiles logged by name: tag cell seed trace limit
run0() {
  side=$1; cell=$2; seed=$3; trace=$4; limit=$5
  tag=$side-$cell-$seed-t$trace
  extra=""; if [ "$trace" = 1 ]; then extra="--save-trace $OUT/$tag.trace.json"; fi
  s0=$(date +%s)
  (cd "$DIR" && JAX_LOG_COMPILES=1 timeout -k 10 $limit python3 bench/run.py --workload $cell --seed $seed --seconds 51 --trace $trace $extra) \
    >"$OUT/$tag.out" 2>"$OUT/$tag.err"
  rc=$?
  echo "run $tag rc $rc wall_s $(( $(date +%s) - s0 ))"
  grep -h "^setup " "$OUT/$tag.out" | cut -c1-400
  grep -h "^request " "$OUT/$tag.out" | head -20 | cut -c1-120
  grep -h "Finished XLA compilation" "$OUT/$tag.err" | sed 's/.*Finished XLA compilation of \(.*\) in \([0-9.]*\) sec.*/\2 \1/' | sort -n -r | head -8
  grep -c "Finished XLA compilation" "$OUT/$tag.err"
  grep -h "fused string-encode\|^fact \|^check \|RESOURCE_EXHAUSTED" "$OUT/$tag.err" | cut -c1-1200 | head -12
  tail -1 "$OUT/$tag.out" | cut -c1-3500
  if [ "$rc" != 0 ]; then grep -v "XLA compilation\|jaxpr to MLIR\|Compiling " "$OUT/$tag.err" | tail -30 | cut -c1-400; fi
  if [ "$trace" = 1 ] && [ -f "$OUT/$tag.trace.json" ]; then
    python3 benchmarks/calls/pr29_trace.py "$OUT/$tag.trace.json" >"$OUT/$tag.programs.txt" 2>&1; head -40 "$OUT/$tag.programs.txt" | cut -c1-200
    python3 benchmarks/calls/pr32_spans.py "$OUT/$tag.trace.json" | cut -c1-200
    rm -f "$OUT/$tag.trace.json"
  fi
  return $rc
}
V=rowconv-155x1m-strings.to-rows
run0 parent $V 3400000033 0 1500
[ "$(left)" -gt 500 ] && run0 parent $V 3400104759 0 450
[ "$(left)" -gt 500 ] && run0 parent $V 3400209489 1 450
for form in staged fused; do
  if [ "$(left)" -gt 420 ]; then
    (cd "$DIR" && timeout -k 10 400 python3 "$HERE/benchmarks/calls/pr34_forms.py" $form) >"$OUT/forms-$form.out" 2>"$OUT/forms-$form.err"
    echo "forms $form rc $?"; tail -1 "$OUT/forms-$form.out" | cut -c1-1500; grep -v "^E1004\|^W1004\|cpu_aot" "$OUT/forms-$form.err" | tail -5 | cut -c1-600
  fi
done
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
