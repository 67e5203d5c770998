# PR 29, chip call 2: chiprun --chips 1 --timeout 3300 -- bash benchmarks/calls/pr29-call2.sh
# After the bisect (call 1b) put an optimization_barrier behind the limb sums of f64acc's MXU branch:
# (1) call 1 again on the body as it now is: the one program against the eager chain on 24 q1 seeds and F1's q6 data;
# (2) F1 itself: the fused q6 pipeline on F1's two seeds (bench/diag/q6_fault.py), which read -1.0 and -2049.125;
# (3) tpch-sf1.q1, the parent (.bench_checkout/, `git archive 35be169`) and the change, order P C C P, a seed a pair,
#     untraced at 51 s; one traced run of the change that keeps its reduced trace long enough to say what the device
#     does under the one program (benchmarks/calls/pr29_trace.py); a third pair if the call is under 30 minutes.
PR_TAG=pr29; CALL=call2  # (as first written the two were prefixes of the `.` line and CALL was lost after it: the runs went to runs-call.jsonl)
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
python3 benchmarks/calls/pr29_exact.py --out "$OUT/exact-call2.jsonl" 2>"$OUT/exact-call2.err" | cut -c1-330 | tail -40
rc=${PIPESTATUS[0]}; echo "pr29_exact rc=$rc"
if [ "$rc" != 0 ]; then tail -5 "$OUT/exact-call2.err"; exit "$rc"; fi
for seed in 2200007920 2500142543; do
  timeout 600 python3 bench/diag/q6_fault.py $seed 3 2>"$OUT/q6-fault-$seed.err" | tail -2 | cut -c1-600; echo "q6_fault rc=$?"
done
bench_run parent tpch-sf1.q1 2910000019 0
bench_run change tpch-sf1.q1 2910000019 0
bench_run change tpch-sf1.q1 2910104748 0
bench_run parent tpch-sf1.q1 2910104748 0
python3 bench/run.py --workload tpch-sf1.q1 --seed 2910209477 --seconds 51 --trace 1 --save-trace "$OUT/q1-change.trace.json" \
  >"$OUT/q1-change-traced.out" 2>"$OUT/q1-change-traced.err"; echo "traced rc=$?"
tail -1 "$OUT/q1-change-traced.out" | cut -c1-3000
python3 benchmarks/calls/pr29_trace.py "$OUT/q1-change.trace.json" | tee "$OUT/q1-change-trace.txt"
python3 - "$OUT/q1-change.trace.json" <<'PY' | tee "$OUT/q1-change-spans.txt"
import collections, json, sys
t = json.load(open(sys.argv[1])); n = t["requests"]; by = collections.defaultdict(list)
for s in t["spans"]:
    by[s["name"]].append(s["dur_us"])
for name, d in sorted(by.items()):
    print(f"{name:32s} n/req {len(d) / n:5.1f}  ms/req {sum(d) / 1e3 / n:10.2f}")
PY
rm -f "$OUT/q1-change.trace.json"
if [ $(( $(date +%s) - t0 )) -lt 1800 ]; then
  bench_run parent tpch-sf1.q1 2910314206 0
  bench_run change tpch-sf1.q1 2910314206 0
fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-call2.jsonl"
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
