#!/bin/sh
# PR 37's chip calls after the layout alone (pr37_layout.py). First, unless STEP=0, one step of cells 4 and 5 with the
# parent's layout and with the tree's over one set of weights (pr37_layout.py step: answers bit for bit, device time by
# scope, the three kernels' own times, the layout's instructions by name). Then parent (.bench_parent: git archive of
# e0f2849, this tree's BENCHMARK.json and benchmarks/ laid over it) against the change (C: the tree this runs from, or
# .bench_archive: git archive $(git write-tree)) in pairs on one seed, through pr33_pairs.sh: cell 4 (the claim) N4
# pairs, cell 5 N5, and as the control, which runs no routed layer, cell 6 N6 (CONTROL names another cell); with
# TRACES=1 one `--trace 1` run of each side in cells 4 and 5. Every run's output is chiprun_out/<T><cell>_*.{out,err}.
#   call 2 (the tree on the way):  chiprun --timeout 3400 -- env TRACES=1 sh tools/chip_calls/pr37_pairs.sh
#   the final tree:  chiprun --timeout 3000 -- env C=.bench_archive T=c37f B=2147750000 STEP=0 N4=2 N5=1 N6=0 TRACES=1 sh tools/chip_calls/pr37_pairs.sh
# S=2 R=1 STEP=0 N4=1 N5=1 N6=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
mkdir -p chiprun_out
B=${B:-2147740000}; T=${T:-c37b}; export S R C
if [ "${STEP:-1}" = 1 ]; then
  for cell in qwen3next axk1; do
    python3 tools/chip_calls/pr37_layout.py step $cell > chiprun_out/${T}_step_$cell.out 2> chiprun_out/${T}_step_$cell.err
    echo "step $cell rc=$?"; grep -vE "^ +[0-9.]+ +[0-9]+ +[0-9.]+ " chiprun_out/${T}_step_$cell.out
  done
fi
env W=qwen3next_score_stream PAIRS=${N4:-3} T=${T}4 SEED0=$B TRACES=$TRACES sh tools/chip_calls/pr33_pairs.sh
env W=axk1_score_stream PAIRS=${N5:-2} T=${T}5 SEED0=$((B + 1000)) TRACES=$TRACES sh tools/chip_calls/pr33_pairs.sh
env W=${CONTROL:-ouro_score_stream} PAIRS=${N6:-1} T=${T}6 SEED0=$((B + 2000)) TRACES= sh tools/chip_calls/pr33_pairs.sh
