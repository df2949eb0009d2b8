#!/bin/sh
# PR 33's chip calls. Parent (.bench_parent: git archive of d1cff70, this tree's BENCHMARK.json and benchmarks/ laid
# over it) against the change (C: the tree this runs from, or .bench_archive: git archive $(git write-tree)), in the
# cells of W, tracing off, one seed for both sides of a pair and the order alternating; then, with TRACES=1, one
# `--trace 1` run of each side on one seed. Every number is printed as the run gave it, unrounded: `centred_err_max`
# and the `moe.*` metrics have to be the parent's to the last digit. Each run goes to chiprun_out/<tag>.{out,err}.
#   chiprun --timeout 3400 -- env PAIRS=3 TRACES=1 sh tools/chip_calls/pr33_pairs.sh
#   chiprun --timeout 1200 -- env W=inceptionv3_featurize_stream PAIRS=1 sh tools/chip_calls/pr33_pairs.sh
# S=2 R=1 PAIRS=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"
W=${W:-axk1_score_stream qwen3next_score_stream}; S=${S:-30}; R=${R:-0}; C=${C:-.}; T=${T:-c33}; B=${SEED0:-2147633000}
show() {
  grep -E "^setup" "$OUT/$1.out" | cut -c1-170; grep -E "^correct" "$OUT/$1.err"
  tail -n 1 "$OUT/$1.out" | python3 -c "
import json, sys
r = json.loads(sys.stdin.read())
print('$1', {k: v['value'] for k, v in r['metrics'].items()}, 'correct', r['correct'], 'failed', r['failed'],
      'peak', r['device'].get('memory_peak_bytes'))
print('   compared', {k: v['value'] for k, v in r.get('compared', {}).items()})
for op in r.get('breakdown', {}).get('device_ops', [])[:10]: print('   op', op)"
}
run() {  # run <dir> <tag> <workload> <seed> <trace>
  t0=$(date +%s)
  ( cd "$1" && python3 benchmarks/run.py --workload $3 --seed $4 --seconds $S --trace $5 --rehearsal $R > "$OUT/$2.out" 2> "$OUT/$2.err"; echo "$2 rc=$? wall=$(( $(date +%s) - t0 )) s" )
  show $2
}
n=0
for w in $W; do
  n=$((n+1)); i=1
  while [ $i -le ${PAIRS:-3} ]; do
    seed=$((B + 100 * n + i))
    if [ $((i % 2)) -eq 1 ]; then run .bench_parent ${T}_${n}_p_$i $w $seed 0; run $C ${T}_${n}_c_$i $w $seed 0
    else run $C ${T}_${n}_c_$i $w $seed 0; run .bench_parent ${T}_${n}_p_$i $w $seed 0; fi
    i=$((i+1))
  done
  if [ -n "$TRACES" ]; then
    seed=$((B + 100 * n + 21))
    run $C ${T}_${n}_c_r1 $w $seed 1; run .bench_parent ${T}_${n}_p_r1 $w $seed 1
  fi
done
