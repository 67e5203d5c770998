# PR 26 and 27 chip calls: shared by pr26-call*.sh and pr27-call*.sh (sourced; PR_TAG names the output directory). Parent and change run from two
# checkouts inside one copy: the change is the tree itself, the parent is .bench_checkout/
# (git archive of the parent commit with this PR's BENCHMARK.json, bench/ and
# benchmarks/trace_cost.py laid over it, made before the call: the copy holds no .git).
# Call 3 sets CHANGE_DIR to .smoke_checkout/, a git archive of the final tree: the committed files are enough.
# One compile cache for both sides, so neither pays the other's cold compile.
set -x
HERE=$PWD
OUT=$HERE/chiprun_out/${PR_TAG:-pr26}
mkdir -p "$OUT"
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$HERE/.jax_cache}
side_dir() { if [ "$1" = parent ]; then echo "$HERE/.bench_checkout"; else echo "${CHANGE_DIR:-$HERE}"; fi; }

# one run of the benchmark: side, cell, seed, trace (0|1); the whole stdout is kept, the
# last line goes to $OUT/runs-$CALL.jsonl (a file a call: the merge back overwrites same names) with its side and seed; a traced run keeps its spans
bench_run() {
  side=$1; cell=$2; seed=$3; trace=$4
  tag=$side-$cell-$seed-t$trace
  extra=""
  if [ "$trace" = 1 ]; then extra="--save-trace $OUT/$tag.trace.json"; fi
  (cd "$(side_dir $side)" && python3 bench/run.py --workload $cell --seed $seed --seconds 51 --trace $trace $extra) \
    >"$OUT/$tag.out" 2>"$OUT/$tag.err"
  rc=$?
  python3 - "$side" "$cell" "$seed" "$trace" "$rc" "$OUT/$tag.out" "$OUT/$tag.trace.json" >>"$OUT/runs-${CALL:-call}.jsonl" <<'PY'
import json, os, sys
side, cell, seed, trace, rc, out, saved = sys.argv[1:]
lines = open(out).read().strip().splitlines()
try:
    res = json.loads(lines[-1])
except (ValueError, IndexError):
    res = None
rec = {"side": side, "cell": cell, "seed": int(seed), "trace": int(trace), "rc": int(rc), "result": res,
       "lines": [ln for ln in lines[:-1] if ln.startswith(("request ", "setup "))]}
if os.path.exists(saved):  # keep the spans, drop the device events (tens of MB in q1)
    t = json.load(open(saved))
    rec["requests"], rec["spans"], rec["window_ns"] = t["requests"], t["spans"], t["window_ns"]
    if not os.environ.get("KEEP_TRACE"):  # PR 31: a call that reduces the device events itself removes the file
        os.remove(saved)
print(json.dumps(rec))
PY
  tail -c 1500 "$OUT/$tag.out" | tail -1 | cut -c1-1200
  if [ "$rc" != 0 ]; then tail -30 "$OUT/$tag.err"; fi
}
