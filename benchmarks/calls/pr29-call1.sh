# PR 29, chip call 1: chiprun --chips 1 --timeout 2700 -- bash benchmarks/calls/pr29-call1.sh
# The one program of a FLOAT64 sum or mean against the un-jitted chain, lane for lane, on the chip: q1's seven
# aggregates on 24 seeds (F1's two among them) and q6's price x discount on F1's two (benchmarks/calls/pr29_exact.py).
set -x
mkdir -p chiprun_out
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"; ls "${JAX_COMPILATION_CACHE_DIR:-.jax_cache}" 2>/dev/null | wc -l
time python3 benchmarks/calls/pr29_exact.py 2>chiprun_out/pr29_exact.err | cut -c1-330
rc=${PIPESTATUS[0]}; echo "pr29_exact rc=$rc"
tail -5 chiprun_out/pr29_exact.err
ls "${JAX_COMPILATION_CACHE_DIR:-.jax_cache}" 2>/dev/null | wc -l
exit $rc
