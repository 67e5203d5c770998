# PR 27, chip call 2 (the proof, on the final tree): chiprun --chips 1 --timeout 3000 -- bash benchmarks/calls/pr27-call2.sh
# The change runs from .smoke_checkout/, a `git archive $(git write-tree)` copy made before the call: the committed
# files are enough. Three more untraced pairs of rowconv on fresh seeds (P C C P P C), one traced run and the
# counters from the copy, then two untraced pairs of tpch-sf1.q1 (C P P C), which never enters sidecar.py.
CALL=call2
PR_TAG=pr27
CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
C=rowconv-212x1m.to-rows
bench_run parent $C 2740000003 0
bench_run change $C 2740000003 0
bench_run change $C 2740104732 0
bench_run parent $C 2740104732 0
bench_run parent $C 2740209461 0
bench_run change $C 2740209461 0
bench_run change $C 2750000017 1
(cd "$CHANGE_DIR" && python3 benchmarks/calls/pr27_counters.py --requests 4 --seed 2760000029) >"$OUT/counters-$CALL.out" 2>"$OUT/counters-$CALL.err"
tail -1 "$OUT/counters-$CALL.out"
Q=tpch-sf1.q1
bench_run change $Q 2770000009 0
bench_run parent $Q 2770000009 0
bench_run parent $Q 2770104738 0
bench_run change $Q 2770104738 0
ps aux | grep "[s]park_rapids_jni_tpu.sidecar" | wc -l
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-400
