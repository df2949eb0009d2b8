#!/bin/sh
# The parent against the change on one seed: for each of cells 4, 5 and 6 one `--trace 1` run in each checkout through
# observed.py (the compared values, the driver's observed counters, the per-layer readings), then one warm `--trace 0`
# run of cell 5 in each for its set-up marks. Every run's output is chiprun_out/<side>_<cell>.{out,err}.
#   P: the parent's checkout (default .bench_parent: git archive of the parent commit); C: the change's (default: here)
#   on a machine with one chip, from the root of the repo:  SEED=2147790011 sh benchmarks/tools/calls/fold_parity.sh
# S=1 R=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
HERE=$(pwd); OUT=$HERE/chiprun_out; mkdir -p "$OUT"
P=${P:-.bench_parent}; C=${C:-.}; SEED=${SEED:-2147790011}; S=${S:-30}; R=${R:-0}
run() {  # run <tag> <checkout> <workload> <trace>
  (cd "$2" && python3 "$HERE/benchmarks/tools/calls/observed.py" --workload "$3" --seed "$SEED" --seconds "$S" \
     --trace "$4" --rehearsal "$R") > "$OUT/$1.out" 2> "$OUT/$1.err"
  echo "$1 rc=$?"
  grep -E "^setup:|^window:" "$OUT/$1.out"
  grep -E "^observed:|^compared|^correct" "$OUT/$1.err"
  tail -1 "$OUT/$1.out" | cut -c1-2500
}
for cell in qwen3next_score_stream axk1_score_stream ouro_score_stream; do
  run parent_$cell "$P" $cell 1
  run change_$cell "$C" $cell 1
done
run parent_axk1_warm "$P" axk1_score_stream 0
run change_axk1_warm "$C" axk1_score_stream 0
