#!/bin/sh
# One cell of the final tree, from .bench_archive (git archive $(git write-tree)), through sets.py: two sets of six
# `--trace 0` runs on the same six seeds, then three `--trace 1` runs and three more `--trace 0` runs on seeds of
# their own, so that twelve seeds read `correct`. Result lines: chiprun_out/<cell>_{set1,set2,traced,more}.jsonl.
#   on a machine with one chip, from the root of the repo:  W=qwen3next_score_stream B=2147791000 sh benchmarks/tools/calls/fold_sets.sh
# PARENT=1 adds three runs of the parent (.bench_parent: git archive of the parent commit) for its set-up marks.
# REHEARSAL=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
W=${W:?workload}; B=${B:?first seed}
. benchmarks/tools/calls/common.sh
SIX=$(seq -s, "$B" $((B + 5)))
sets "$W" "$SIX" "${W}_set1" 0
sets "$W" "$SIX" "${W}_set2" 0
sets "$W" "$(seq -s, $((B + 10)) $((B + 12)))" "${W}_traced" 1
sets "$W" "$(seq -s, $((B + 20)) $((B + 22)))" "${W}_more" 0
if [ -n "$PARENT" ]; then  # the parent's set-up marks, from ../.bench_parent: its checkout's first run, then two more
  for k in 1 2 3; do
    (cd ../.bench_parent && python3 benchmarks/run.py --workload "$W" --seed $((B + 30 + k)) --seconds $S \
       --trace 0 --rehearsal $R) > "$OUT/${W}_parent_$k.out" 2> "$OUT/${W}_parent_$k.err"
    echo "parent $k rc=$?"; grep "^setup:" "$OUT/${W}_parent_$k.out"; tail -1 "$OUT/${W}_parent_$k.out" | cut -c1-300
  done
fi
