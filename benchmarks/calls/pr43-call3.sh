# Chip call 3, on a host with one TPU, from the root of a checkout (~11 min): bash benchmarks/calls/pr43-call3.sh
# The store star twice more, in the order C P P C, a seed a pair, untraced at 51 s: its latency and set-up beside
# call 2's (the compile cache the machine keeps from call 2 holds both sides' programs).
PR_TAG=pr43; CALL=${CALL:-call3}; CHANGE_DIR=${CHANGE_DIR:-$PWD/.smoke_checkout}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-900} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
ST=tpcds-sf1-store.q3-q55; A=4301070937; B=4301175673
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run change $ST $A 0; facts change-$ST-$A-t0
if [ "$(left)" -gt 200 ]; then bench_run parent $ST $A 0; facts parent-$ST-$A-t0; fi
if [ "$(left)" -gt 200 ]; then bench_run parent $ST $B 0; facts parent-$ST-$B-t0; fi
if [ "$(left)" -gt 170 ]; then bench_run change $ST $B 0; facts change-$ST-$B-t0; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -12
