# PR 26, chip call 2: chiprun --chips 1 --timeout 3000 -- bash benchmarks/calls/pr26-call2.sh
# rowconv-212x1m.to-rows: untraced parent against change (4 runs a side, a seed a pair) and traced runs of both.
CALL=call2
. benchmarks/calls/pr26-common.sh
C=rowconv-212x1m.to-rows
bench_run change $C 2640000007 0
bench_run parent $C 2640000007 0
bench_run parent $C 2640104736 0
bench_run change $C 2640104736 0
bench_run change $C 2640209465 0
bench_run parent $C 2640209465 0
bench_run parent $C 2640314194 0
bench_run change $C 2640314194 0
bench_run parent $C 2650000021 1
bench_run change $C 2650000021 1
bench_run change $C 2650104750 1
bench_run parent $C 2650104750 1
ps aux | grep "[s]park_rapids_jni_tpu.sidecar" | wc -l
ls -la "$OUT" | tail -40
