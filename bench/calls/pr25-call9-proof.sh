# PR 25, chip call 9, the proof that the committed files are enough: run from a git-archive copy of the final tree,
# unpacked into the ignored .bench_checkout/: chiprun --chips 1 --timeout 1700 -- bash .bench_checkout/proof.sh
set -x
cd .bench_checkout
python3 bench/run.py --workload rowconv-212x1m.to-rows --seed 3811222333 --seconds 51 --trace 0 2>/dev/null | grep -v "^request" | tail -2 | cut -c1-900
python3 bench/run.py --workload rowconv-212x1m.to-rows --seed 3811222444 --seconds 51 --trace 1 2>/dev/null | tail -1 | cut -c1-1200
python3 bench/run.py --workload tpch-sf1.q1 --seed 3811222555 --seconds 51 --trace 0 2>/dev/null | grep -v "^request" | tail -2 | cut -c1-900
python3 bench/run.py --workload tpch-sf1.q1 --seed 3811222666 --seconds 51 --trace 1 2>/dev/null | tail -1 | cut -c1-1800
echo leftover workers: $(ps aux | grep -c "[s]park_rapids_jni_tpu.sidecar")
