# PR 28, chip call 2: chiprun --chips 4 --timeout 1000 -- bash benchmarks/calls/pr28-call2.sh
# The new cell warm, untraced, 51 s: four runs, a seed each (with call 1's cold run: five untraced runs, five seeds).
# The budget (150 chip-minutes, 85 of them spent by call 1's 16-minute cold compile on four chips) does not hold the
# two sets of six that the driver makes; each run holds the four chips for ~2.5 minutes.
set -x
mkdir -p chiprun_out
python3 bench/measure.py --workload tpcds-sf10-web.q95-x4 --seconds 51 --sets 1 --runs 4 --first-seed 2500000041 --out chiprun_out/pr28_sets.jsonl
