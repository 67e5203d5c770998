# PR 36, chip call 2 (one chip): chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr36-call2.sh
# Parent (.bench_checkout/: `git archive 9dec224` with this PR's BENCHMARK.json, bench/ and benchmarks/ laid over it)
# against change (CHANGE_DIR, default the tree; .smoke_checkout/ is a `git archive $(git write-tree)`).
# Traced runs first (each read through benchmarks/calls/pr36_attribution.py): the change in q1, the store star and
# rowconv, and the PARENT traced in q1 and the store star on the same seeds — the cost of the new records with
# tracing ON. Then with tracing OFF one pair of q1 and of the store star at 51 s (pr32_run.py: the xla counters of
# each side's set-up say whether any program missed the cache).
PR_TAG=pr36; CALL=${CALL:-call2}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-3300} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
Q1=tpch-sf1.q1; S=tpcds-sf1-store.q3-q55; RC=rowconv-212x1m.to-rows
TQ=${SEED_TQ:-3600104729}; TS=${SEED_TS:-3600418897}; TR_=${SEED_TR:-3600209441}; A=${SEED_A:-3600523633}; B=${SEED_B:-3600628373}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
traced() {  # side cell seed
  KEEP_TRACE=1 bench_run $1 $2 $3 1
  TR="$OUT/$1-$2-$3-t1.trace.json"
  if [ -f "$TR" ]; then
    python3 benchmarks/calls/pr36_attribution.py "$TR" >"$OUT/$1-$2-$3-t1.attribution.txt" 2>&1
    head -${HEAD:-70} "$OUT/$1-$2-$3-t1.attribution.txt" | cut -c1-200
    python3 benchmarks/calls/pr29_trace.py "$TR" >"$OUT/$1-$2-$3-t1.programs.txt" 2>&1
    gzip -f "$TR"
  fi
  grep -h "^request" "$OUT/$1-$2-$3-t1.out" | awk '{print $8}' | sort -n | awk '{a[NR]=$1} END {print "traced latency median ms", a[int((NR+1)/2)], "of", NR}'
}
counted() {  # side cell seed: an untraced run at 51 s through pr32_run.py
  tag=$1-$2-$3-t0
  (cd "$(side_dir $1)" && python3 benchmarks/calls/pr32_run.py --workload $2 --seed $3 --seconds 51 --trace 0) >"$OUT/$tag.out" 2>"$OUT/$tag.err"
  rc=$?
  python3 - "$1" "$2" "$3" "$rc" "$OUT/$tag.out" "$OUT/$tag.err" >>"$OUT/runs-$CALL.jsonl" <<'PY'
import json, sys
side, cell, seed, rc, out, err = sys.argv[1:]
lines = open(out).read().strip().splitlines()
try:
    res = json.loads(lines[-1])
except (ValueError, IndexError):
    res = None
xla = [ln.strip() for ln in open(err) if ln.startswith("[pr32] xla")]
print(json.dumps({"side": side, "cell": cell, "seed": int(seed), "trace": 0, "rc": int(rc), "result": res, "xla": xla[-1:],
                  "lines": [ln for ln in lines[:-1] if ln.startswith(("request ", "setup "))]}))
PY
  tail -1 "$OUT/$tag.out" | cut -c1-600; grep -h "^\[pr32\] xla" "$OUT/$tag.err" | tail -1; facts $tag
  if [ "$rc" != 0 ]; then tail -30 "$OUT/$tag.err"; fi
}
traced change $Q1 $TQ
if [ "$(left)" -gt 300 ]; then traced change $RC $TR_; fi
if [ "$(left)" -gt 900 ]; then HEAD=90 traced change $S $TS; fi
if [ "$(left)" -gt 300 ]; then traced parent $Q1 $TQ; fi
if [ "$(left)" -gt 700 ]; then traced parent $S $TS; fi
if [ "$(left)" -gt 400 ]; then counted parent $Q1 $A; counted change $Q1 $A; fi
if [ "$(left)" -gt 700 ]; then counted parent $S $B; fi
if [ "$(left)" -gt 300 ]; then counted change $S $B; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -60
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
