# PR 26, chip call 1: chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr26-call1.sh
# tpch-sf1.q1: untraced parent against change (4 runs a side, a seed a pair), traced runs of both,
# and what tracing costs a request (profiler alone, spans alone, both) on both sides.
CALL=call1
. benchmarks/calls/pr26-common.sh
C=tpch-sf1.q1
bench_run change $C 2610000013 0     # also warms the one compile cache: its setup_s may hold a cold compile
bench_run parent $C 2610000013 0
bench_run parent $C 2610104742 0
bench_run change $C 2610104742 0
bench_run change $C 2610209471 0
bench_run parent $C 2610209471 0
bench_run parent $C 2610314200 0
bench_run change $C 2610314200 0
bench_run parent $C 2620000019 1
bench_run change $C 2620000019 1
bench_run change $C 2620104748 1
bench_run parent $C 2620104748 1
(cd "$(side_dir change)" && python3 benchmarks/trace_cost.py --seed 2630000029 --requests 3 --out "$OUT/trace_cost_change.json") 2>"$OUT/trace_cost_change.err" | tail -1 | cut -c1-3000
(cd "$(side_dir parent)" && python3 benchmarks/trace_cost.py --seed 2630000029 --requests 3 --out "$OUT/trace_cost_parent.json") 2>"$OUT/trace_cost_parent.err" | tail -1 | cut -c1-3000
ls -la "$OUT" | tail -40
