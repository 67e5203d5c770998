# PR 26, chip call 3 (the proof, on the final tree): chiprun --chips 1 --timeout 3000 -- bash benchmarks/calls/pr26-call3.sh
# The change runs from .smoke_checkout/, a `git archive $(git write-tree)` copy made before the call: the committed
# files are enough. Three more untraced pairs of rowconv (the change was the slower side in all four pairs of call 2),
# one untraced q1, and one traced run of each cell.
CALL=call3
CHANGE_DIR=$PWD/.smoke_checkout
. benchmarks/calls/pr26-common.sh
C=rowconv-212x1m.to-rows
bench_run parent $C 2660000003 0
bench_run change $C 2660000003 0
bench_run change $C 2660104732 0
bench_run parent $C 2660104732 0
bench_run parent $C 2660209461 0
bench_run change $C 2660209461 0
bench_run change $C 2670000017 1
bench_run change tpch-sf1.q1 2680000009 0
bench_run change tpch-sf1.q1 2690000023 1
ps aux | grep "[s]park_rapids_jni_tpu.sidecar" | wc -l
ls -la "$OUT" | tail -15
