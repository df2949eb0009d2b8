#!/bin/sh
# Cell 4 on one TPU host, parent commit against this tree. First, unless STEP=0, pr43_profile.py: cell 4's step
# with the parent's delta-rule block, the one-product lead and the tree's, over one set of weights (answers bit for
# bit, device time by scope, the instructions of GatedDeltaNet_0 in chiprun_out/<T>_profile.out). Then, through
# pr33_pairs.sh, parent (.bench_parent: git archive of dbf68fb) against the change (C: the tree this runs from, or
# .bench_archive: git archive $(git write-tree)) in cell 4, PAIRS pairs on a seed each and, with TRACES=1, one
# `--trace 1` run of each side. Every run's output is chiprun_out/<T>4_*.{out,err}.
#   env C=.bench_archive TRACES=1 sh tools/chip_calls/pr43_pairs.sh
# S=2 R=1 PAIRS=1 ROWS=4 REHEARSAL=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic file's rehearsal sizes.
mkdir -p chiprun_out
T=${T:-c43}; export S R C
if [ "${STEP:-1}" = 1 ]; then
  python3 tools/chip_calls/pr43_profile.py ${SEED:-2147743001} > chiprun_out/${T}_profile.out 2> chiprun_out/${T}_profile.err
  echo "profile rc=$?"; grep -vE "^ +[0-9.]+ +[0-9]+ +[0-9.]+ " chiprun_out/${T}_profile.out
fi
env W=qwen3next_score_stream PAIRS=${PAIRS:-2} T=${T}4 SEED0=${B:-2147743100} TRACES=$TRACES sh tools/chip_calls/pr33_pairs.sh
