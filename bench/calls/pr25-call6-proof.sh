# PR 25, chip call 6, as run from a git-archive copy: chiprun --chips 1 -- bash .bench_checkout/proof.sh
# Exit code 1 is the last line's: grep -c prints 0 (no worker left behind) and exits 1 when it counts nothing.
set -x
cd .bench_checkout
for w in tpcds-sf1-store.q3-q55 tpch-sf1.q1 rowconv-212x1m.to-rows; do
python3 bench/run.py --workload $w --seed 3111222333 --seconds 51 --trace 0 2>/dev/null | grep -v "^request" | tail -2 | cut -c1-900
done
python3 bench/run.py --workload tpcds-sf1-store.q3-q55 --seed 3111222444 --seconds 51 --trace 1 2>/dev/null | tail -1 | cut -c1-1500
python3 bench/run.py --workload rowconv-212x1m.to-rows --seed 3111222444 --seconds 51 --trace 1 2>/dev/null | tail -1 | cut -c1-1500
ps aux | grep -c "[s]park_rapids_jni_tpu.sidecar"
