# PR 29, chip call 1b: chiprun --chips 1 --timeout 1800 -- bash benchmarks/calls/pr29-call1b.sh
# Call 1 found the one program wrong on 3 of 168 aggregates (F1's signature): the bisect. Cuts of the body against the
# eager chain on the seeds that failed, as q1 groups them and under random regroupings (benchmarks/calls/pr29_bisect.py).
set -x
mkdir -p chiprun_out
time python3 benchmarks/calls/pr29_bisect.py 2>chiprun_out/pr29_bisect.err | cut -c1-700
tail -5 chiprun_out/pr29_bisect.err
