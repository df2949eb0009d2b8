#!/bin/sh
# PR 29's chip calls. Parent (.bench_parent: git archive of 3d9c7a6) against the change (C: the tree this
# runs from, or .bench_archive: git archive $(git write-tree)), tracing off, one seed for both sides of a
# pair and the order alternating; then, with TRACES=1, one `--trace 1` run and one `traced.py` run of each
# side. Each run goes to chiprun_out/<tag>.{out,err}; the last line of .out is the result. As sent:
#   call 1, cell 4, six pairs:  chiprun --timeout 3500 -- env PAIRS=6 sh tools/chip_calls/pr29_pairs.sh
#   call 2, cell 4 traced, then a pair of cell 1 and the profile of one step:
#       chiprun --timeout 3000 -- sh -c 'env PAIRS=0 TRACES=1 SEED0=2147501000 sh tools/chip_calls/pr29_pairs.sh;
#         env W=inceptionv3_featurize_stream PAIRS=1 SEED0=2147502000 T=c29i sh tools/chip_calls/pr29_pairs.sh;
#         python3 tools/chip_calls/pr28_profile.py 3'
#   call 3, cell 3, one pair:  chiprun --chips 4 --timeout 1800 -- env W=inceptionv3_featurize_stream_x4 PAIRS=1 \
#       SEED0=2147503000 T=c29x sh tools/chip_calls/pr29_pairs.sh
#   call 4, the final tree as git would commit it (git archive $(git write-tree) | tar -x -C .bench_archive):
#       chiprun --timeout 3000 -- env C=.bench_archive PAIRS=2 TRACES=1 SEED0=2147504000 T=c29f sh tools/chip_calls/pr29_pairs.sh
# S=2 R=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"; ROOT=$PWD
W=${W:-qwen3next_score_stream}; S=${S:-30}; R=${R:-0}; C=${C:-.}; T=${T:-c29}; B=${SEED0:-2147500000}
show() { grep -E "^(setup|window)" "$OUT/$1.out" | cut -c1-170; grep -E "^compared centred_err_max|^correct" "$OUT/$1.err"; tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],4) for k,v in r['metrics'].items()}, r['correct'], r['failed'], r['device'].get('memory_peak_bytes')); b=r.get('breakdown',{}); print(b.get('device_blocks')); print(b.get('idle_gaps'))"; }
run() {  # run <dir> <tag> <seed> [<trace>]
  ( cd "$1" && python3 benchmarks/run.py --workload $W --seed $3 --seconds $S --trace ${4:-0} --rehearsal $R > "$OUT/$2.out" 2> "$OUT/$2.err"; echo "$2 rc=$?" )
  show $2
}
traced() {  # traced <dir> <tag> <seed>
  ( cd "$1" && python3 benchmarks/traced.py --workload $W --seed $3 --seconds $S --rehearsal $R > "$OUT/$2.out" 2> "$OUT/$2.err"; echo "$2 rc=$?" )
  show $2
}
i=1
while [ $i -le ${PAIRS:-6} ]; do
  if [ $((i % 2)) -eq 1 ]; then run .bench_parent ${T}_p_$i $((B+i)); run $C ${T}_c_$i $((B+i))
  else run $C ${T}_c_$i $((B+i)); run .bench_parent ${T}_p_$i $((B+i)); fi
  i=$((i+1))
done
if [ -n "$TRACES" ]; then
  run .bench_parent ${T}_p_r1 $((B+21)) 1; run $C ${T}_c_r1 $((B+21)) 1
  traced $C ${T}_c_t $((B+22)); traced .bench_parent ${T}_p_t $((B+22))
fi
