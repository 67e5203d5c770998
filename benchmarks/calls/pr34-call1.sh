# PR 34, chip call 1: chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr34-call1.sh
# The CHANGE (this tree) against the PARENT (.bench_checkout/: `git archive fc974bf` with this PR's BENCHMARK.json and
# bench/ laid over it) on the new cell: the change traced (the trace kept for the kernels' device time), P, the change's
# first set of six seeds (bench/measure.py), P, bench/control.py, then one pair of rowconv-212x1m.to-rows.
PR_TAG=pr34; CALL=${CALL:-call1}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-3500} - ( $(date +%s) - t0 ) )); }
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
# one run with the worker's compiles logged by name: side cell seed trace limit
run0() {
  side=$1; cell=$2; seed=$3; trace=$4; limit=$5
  tag=$side-$cell-$seed-t$trace
  extra=""; if [ "$trace" = 1 ]; then extra="--save-trace $OUT/$tag.trace.json"; fi
  s0=$(date +%s)
  (cd "$(side_dir $side)" && JAX_LOG_COMPILES=1 timeout -k 10 $limit python3 bench/run.py --workload $cell --seed $seed --seconds 51 --trace $trace $extra) \
    >"$OUT/$tag.out" 2>"$OUT/$tag.err"
  rc=$?
  echo "run $tag rc $rc wall_s $(( $(date +%s) - s0 ))"
  grep -h "^setup " "$OUT/$tag.out" | cut -c1-400
  grep -h "^request " "$OUT/$tag.out" | head -12 | cut -c1-90
  grep -h "Finished XLA compilation" "$OUT/$tag.err" | sed 's/.*Finished XLA compilation of \(.*\) in \([0-9.]*\) sec.*/\2 \1/' | sort -n -r | head -4
  grep -c "Finished XLA compilation" "$OUT/$tag.err"
  grep -h "fused string-encode\|^fact \|RESOURCE_EXHAUSTED" "$OUT/$tag.err" | cut -c1-1200 | head -6
  grep -h "^check " "$OUT/$tag.err" | grep -v " ok$"
  tail -1 "$OUT/$tag.out" | cut -c1-3500
  if [ "$rc" != 0 ]; then grep -v "XLA compilation\|jaxpr to MLIR\|Compiling " "$OUT/$tag.err" | tail -30 | cut -c1-400; fi
  if [ "$trace" = 1 ] && [ -f "$OUT/$tag.trace.json" ]; then
    python3 benchmarks/calls/pr29_trace.py "$OUT/$tag.trace.json" >"$OUT/$tag.programs.txt" 2>&1; head -36 "$OUT/$tag.programs.txt" | cut -c1-200
    python3 benchmarks/calls/pr34_kernels.py "$OUT/$tag.trace.json" | tee "$OUT/$tag.kernels.txt" | cut -c1-200
    python3 benchmarks/calls/pr32_spans.py "$OUT/$tag.trace.json" | cut -c1-200
    python3 - "$OUT/$tag.trace.json" <<'PY'
import json, sys
t = json.load(open(sys.argv[1]))
for name in ("rowconv.sizes", "rowconv.encode", "op.convert_to_rows"):
    hit = [s for s in t["spans"] if s["name"] == name]
    print(name, len(hit), "spans over", t["requests"], "requests; first:", hit[0].get("annotations") if hit else None,
          "ms each:", [round(s["dur_us"] / 1e3, 2) for s in hit[:6]])
PY
    rm -f "$OUT/$tag.trace.json"
  fi
  return $rc
}
V=rowconv-155x1m-strings.to-rows; F=rowconv-212x1m.to-rows
if [ "${PART:-1}" = 1 ]; then
  run0 change $V 3410000017 1 900
  run0 parent $V 3410104729 0 600
  (cd "$(side_dir change)" && python3 bench/measure.py --workload $V --seconds 51 --sets 1 --runs 6 --first-seed 3411000037 --out "$OUT/measure-$CALL.jsonl") 2>&1 | cut -c1-400
  [ "$(left)" -gt 900 ] && run0 parent $V 3410209441 0 600
  [ "$(left)" -gt 700 ] && (cd "$(side_dir change)" && timeout -k 10 600 python3 bench/control.py --workload $V --seeds 3 --control-seeds 2 --first-seed 3412000001 --seconds 1) 2>"$OUT/control-$CALL.err" | cut -c1-700
  [ "$(left)" -gt 420 ] && run0 parent $F 3413000003 0 400
  [ "$(left)" -gt 220 ] && run0 change $F 3413000003 0 400
else
  (cd "$(side_dir change)" && python3 bench/measure.py --workload $V --seconds 51 --sets 1 --runs 6 --first-seed 3421000051 --out "$OUT/measure-$CALL.jsonl") 2>&1 | cut -c1-400
  run0 change $V 3420000019 1 600
  [ "$(left)" -gt 500 ] && run0 change $F 3423000007 0 400
  [ "$(left)" -gt 400 ] && run0 parent $F 3423000007 0 400
  [ "$(left)" -gt 400 ] && run0 change $F 3423104759 1 400
  Q=tpch-sf1.q1
  [ "$(left)" -gt 1300 ] && run0 parent $Q 3424000009 0 700
  [ "$(left)" -gt 650 ] && run0 change $Q 3424000009 0 600
fi
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
