#!/bin/sh
# PR 34's call on a final tree: .bench_archive (git archive $(git write-tree), made after `git add -A`) in the new cell, one
# `--trace 1` run and two `--trace 0`, each seed its own; then cells 4 and 5, .bench_parent (git archive of a397606, this
# PR's BENCHMARK.json and benchmarks/ laid over it) against .bench_archive, one seed for both sides of a pair
# (tools/chip_calls/pr33_pairs.sh). Outputs in chiprun_out/<T>_*.{out,err}.
#   call 4 (the tree after REVIEW.md), as sent:  chiprun --timeout 2400 -- sh tools/chip_calls/pr34_final.sh
#   call 3 (the first session's final tree): its wrapper was not kept. From its outputs (chiprun_out/c34f_*, c34g_*) it was
#       env C=.bench_archive TRACE1_SEEDS=2147685001 SEEDS="2147685002 2147685003" T=c34f sh tools/chip_calls/pr34_cell.sh
#       env C=.bench_archive W=qwen3next_score_stream PAIRS=2 T=c34g SEED0=<not recorded> sh tools/chip_calls/pr33_pairs.sh
#     which is this file with B=2147685000 T=c34f for its first line; the pairs' seeds are in no output and cannot be given.
# S=2 R=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
B=${B:-2147688000}; T=${T:-c34h}; export S R
env C=.bench_archive TRACE1_SEEDS=$((B + 1)) SEEDS="$((B + 2)) $((B + 3))" T=$T sh tools/chip_calls/pr34_cell.sh
env C=.bench_archive W="${W:-qwen3next_score_stream axk1_score_stream}" PAIRS=${PAIRS:-1} T=${T}p SEED0=$((B + 1000)) \
  sh tools/chip_calls/pr33_pairs.sh
