# PR 27, chip call 1: chiprun --chips 1 --timeout 3000 -- bash benchmarks/calls/pr27-call1.sh
# rowconv-212x1m.to-rows (the claimed cell): untraced parent against change, 4 pairs, a seed a pair, order
# P C C P P C C P; two traced runs of the change and one of the parent; the worker's counters over four requests.
CALL=call1
PR_TAG=pr27
. benchmarks/calls/pr26-common.sh
C=rowconv-212x1m.to-rows
bench_run parent $C 2710000007 0
bench_run change $C 2710000007 0
bench_run change $C 2710104736 0
bench_run parent $C 2710104736 0
bench_run parent $C 2710209465 0
bench_run change $C 2710209465 0
bench_run change $C 2710314194 0
bench_run parent $C 2710314194 0
bench_run change $C 2720000021 1
bench_run parent $C 2720000021 1
bench_run change $C 2720104750 1
python3 benchmarks/calls/pr27_counters.py --requests 4 --seed 2730000011 >"$OUT/counters-$CALL.out" 2>"$OUT/counters-$CALL.err"
tail -1 "$OUT/counters-$CALL.out"
ps aux | grep "[s]park_rapids_jni_tpu.sidecar" | wc -l
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-400
