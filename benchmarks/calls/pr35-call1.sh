# PR 35, chip call 1 (one chip): chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr35-call1.sh
# The lanes before any timing of the cell: q1's deferred and compacted forms at full size, lane for lane over three
# seeds, then the sweep of the share kept that places _DEFER_MIN_KEEP (benchmarks/calls/pr35_forms.py), then how many
# programs a q1 process asks the backend for, parent (.bench_checkout/: `git archive 9c0f675`) against change
# (benchmarks/calls/pr32_run.py: xla.backend_compiles + xla.cache_hits of a short run, whatever the cache holds).
PR_TAG=pr35; CALL=call1; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
python3 benchmarks/calls/pr35_forms.py --budget-s ${FORMS_BUDGET_S:-2300} 2>"$OUT/forms.err" | cut -c1-600
echo "forms rc ${PIPESTATUS[0]}"; grep -v "cpu_aot_loader" "$OUT/forms.err" | tail -5 | cut -c1-300
for side in parent change; do
  (cd "$(side_dir $side)" && python3 benchmarks/calls/pr32_run.py --workload tpch-sf1.q1 --seed 3500314173 --seconds 1 --trace 0) \
    >"$OUT/programs-$side.out" 2>"$OUT/programs-$side.err"
  echo "programs $side rc $?"; grep -h "^\[pr32\]" "$OUT/programs-$side.err" | tail -8 | cut -c1-200
  grep -h "^setup" "$OUT/programs-$side.out" | tail -1 | cut -c1-300
done
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
