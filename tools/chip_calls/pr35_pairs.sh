#!/bin/sh
# PR 35's chip calls after the profile (pr35_profile.py). First, unless ROUNDING=0, which rounding moved
# (pr35_rounding.py). Then parent (.bench_parent: git archive of d781583, this tree's BENCHMARK.json and benchmarks/
# laid over it) against the change (C: the tree this runs from, or .bench_archive: git archive $(git write-tree)) in
# pairs on one seed, through pr33_pairs.sh: cell 5 (the claim) N5 pairs, cell 6 N6, cell 4 N4, and with TRACES=1 one
# `--trace 1` run of each side in each. Every run's output is chiprun_out/<T><cell>_*.{out,err}.
#   call 2 (the tree on the way):  chiprun --timeout 3400 -- env TRACES=1 sh tools/chip_calls/pr35_pairs.sh
#   the final tree:  chiprun --timeout 3000 -- env C=.bench_archive T=c35f B=2147710000 ROUNDING=0 N5=2 N6=1 N4=1 TRACES=1 sh tools/chip_calls/pr35_pairs.sh
# S=2 R=1 N5=1 N6=1 N4=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
mkdir -p chiprun_out
B=${B:-2147700000}; T=${T:-c35b}; export S R C TRACES
if [ "${ROUNDING:-1}" = 1 ]; then
  python3 tools/chip_calls/pr35_rounding.py > chiprun_out/${T}_rounding.out 2> chiprun_out/${T}_rounding.err
  echo "rounding rc=$?"; grep -E "^==|\(1\)|\(2\)|units" chiprun_out/${T}_rounding.out
fi
env W=axk1_score_stream PAIRS=${N5:-3} T=${T}5 SEED0=$B sh tools/chip_calls/pr33_pairs.sh
env W=ouro_score_stream PAIRS=${N6:-2} T=${T}6 SEED0=$((B + 1000)) sh tools/chip_calls/pr33_pairs.sh
env W=qwen3next_score_stream PAIRS=${N4:-1} T=${T}4 SEED0=$((B + 2000)) sh tools/chip_calls/pr33_pairs.sh
