# Chip call 1, on a host with one TPU, from the root of a checkout (~30 min): bash benchmarks/calls/pr43-call1.sh
# The change from .smoke_checkout/ (a `git archive $(git write-tree)` of the tree: the committed files are enough),
# the parent from .bench_checkout/ (its commit with this PR's BENCHMARK.json and bench/ laid over it).
# (1) the Sort alone, both forms, at q1's and the store star's shapes (pr43_sort.py, from the change);
# (2) the claimed cells, tpch-sf1-decimal.q1 then tpch-sf1.q1, each in the order P C C P, a seed a pair, untraced at
# 51 s; (3) one traced run of the change in each: its spans, programs and idle gaps.
PR_TAG=pr43; CALL=${CALL:-call1}; CHANGE_DIR=${CHANGE_DIR:-$PWD/.smoke_checkout}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-2100} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
traced() {  # side cell seed
  KEEP_TRACE=1 bench_run $1 $2 $3 1
  TR="$OUT/$1-$2-$3-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/$1-$2-$3-t1.attribution.txt" 2>&1
    head -30 "$OUT/$1-$2-$3-t1.attribution.txt" | cut -c1-200
    python3 benchmarks/calls/pr29_trace.py "$TR" >"$OUT/$1-$2-$3-t1.programs.txt" 2>&1; head -30 "$OUT/$1-$2-$3-t1.programs.txt" | cut -c1-200
    gzip -f "$TR"
  fi
}
(cd "$CHANGE_DIR" && python3 benchmarks/calls/pr43_sort.py --out "$OUT/sort.jsonl") 2>"$OUT/sort.err" | cut -c1-600
echo "sort rc ${PIPESTATUS[0]}"
DQ=tpch-sf1-decimal.q1; Q1=tpch-sf1.q1
for pair in "$DQ 4300128311 4300233047" "$Q1 4300337783 4300442519"; do
  set -- $pair; cell=$1; A=$2; B=$3
  if [ "$(left)" -gt 500 ]; then bench_run parent $cell $A 0; facts parent-$cell-$A-t0; fi
  if [ "$(left)" -gt 400 ]; then bench_run change $cell $A 0; facts change-$cell-$A-t0; fi
  if [ "$(left)" -gt 400 ]; then bench_run change $cell $B 0; facts change-$cell-$B-t0; fi
  if [ "$(left)" -gt 300 ]; then bench_run parent $cell $B 0; facts parent-$cell-$B-t0; fi
done
if [ "$(left)" -gt 250 ]; then traced change $DQ 4300547261; fi
if [ "$(left)" -gt 250 ]; then traced change $Q1 4300651997; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -60
