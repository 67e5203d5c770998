# PR 33, chip call 1 (ISSUE 33 satellite 2): chiprun --chips 1 --timeout 1500 -- bash benchmarks/calls/pr33-call1.sh
# BEFORE any timing: the stage's one program against the eager evaluator, lane for lane, on one chip, at full size on
# the benchmark's own data, two seeds: q1's Filter mask, disc_price and charge; q95's wh_lo / wh_hi normalisation and the
# wh_lo <> wh_hi Filter (PERF.md 7 (iv): dd_from_f64bits, add2_f64bits and _abs64_to_f64bits are 64-bit shift-and-add
# chains, jitted here for the first time outside the fused q6 pipeline). Also says how many programs the machine's
# compile cache came with: the four-chip calls are planned from that.
set -x
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
mkdir -p chiprun_out/pr33
timeout -k 10 1300 python3 benchmarks/calls/pr33_bits.py --chips 1 --out chiprun_out/pr33/bits-1chip.jsonl 2>chiprun_out/pr33/bits-1chip.err | cut -c1-600
echo "bits rc ${PIPESTATUS[0]}"
grep -v "cpu_aot_loader" chiprun_out/pr33/bits-1chip.err | tail -15 | cut -c1-400
ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l
