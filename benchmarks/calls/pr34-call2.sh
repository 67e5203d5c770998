# PR 34, chip call 2, on the FINAL tree: chiprun --chips 1 --timeout 3500 -- bash benchmarks/calls/pr34-call2.sh
# The change is .smoke_checkout/ (`git archive $(git write-tree)`: the committed files are enough), the parent
# .bench_checkout/ as in call 1: the change's second set of six seeds, a traced run, rowconv-212x1m.to-rows C P and
# C traced (still 4 programs a request), then one pair of tpch-sf1.q1 if the time allows.
export PART=2 CALL=call2 CHANGE_DIR=$PWD/.smoke_checkout
bash benchmarks/calls/pr34-call1.sh
