# Chip call 2, on a host with one TPU, on the final tree (~30 min): bash benchmarks/calls/pr40-call2.sh
# tpcds-sf1-store.q3-q55, unclaimed and expected not to move: its two float64 sums run _f64_sum_mean over 650 and
# 816 rows (compiled for 768 and 896 groups), which loses one scatter over under 1,000 rows. One pair on one seed at
# 51 s, parent (.bench_checkout/: `git archive 1e7cde0`) first: with nothing of the cell in the compile cache it
# sets up cold (~840 s), the change after it warm. Then tpch-sf1.q1 once more from CHANGE_DIR (.smoke_checkout/: a
# `git archive $(git write-tree)`: the committed files are enough), and the parent beside it if time is left.
PR_TAG=pr40; CALL=${CALL:-call2}; CHANGE_DIR=${CHANGE_DIR:-$PWD}
. benchmarks/calls/pr26-common.sh
t0=$(date +%s)
left() { echo $(( ${CALL_SECONDS:-1750} - ( $(date +%s) - t0 ) )); }
facts() { grep -h "^setup" "$OUT/$1.out" | cut -c1-300 | tail -1; }
S=tpcds-sf1-store.q3-q55; Q1=tpch-sf1.q1
SS=${SEED_S:-4000523621}; QC=${SEED_QC:-4000628353}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
bench_run parent $S $SS 0; facts parent-$S-$SS-t0
if [ "$(left)" -gt 330 ]; then bench_run change $S $SS 0; facts change-$S-$SS-t0; fi
if [ "$(left)" -gt 150 ]; then bench_run change $Q1 $QC 0; facts change-$Q1-$QC-t0; fi
if [ "$(left)" -gt 150 ]; then bench_run parent $Q1 $QC 0; facts parent-$Q1-$QC-t0; fi
python3 benchmarks/calls/pr26_summary.py "$OUT/runs-$CALL.jsonl" | cut -c1-300 | head -40
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
