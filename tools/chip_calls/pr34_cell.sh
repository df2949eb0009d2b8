#!/bin/sh
# PR 34's chip calls for the cell ouro_score_stream: pr32_cell.sh with this cell's files named, and first the
# parent's try of the new cell. Each run goes to chiprun_out/<tag>.{out,err}; the last line of .out is the result.
#   PARENT=1        .bench_parent (git archive of the parent, this PR's BENCHMARK.json and benchmarks/ laid over it) is
#                   asked for the cell first: it has to end at once, with another exit code than 0
#   SEEDS="a b"     one run a seed, TRACE=0|1;  TRACE1_SEEDS="c"  further --trace 1 runs
#   FAULTS="a b"    the int8 control and both planted faults on those seeds, FAULT_ROWS rows each, FAULT_ONLY=int8 for
#                   the control alone (tools/chip_calls/pr34_faults.py; PROGRAM=1 reads the program on the same rows)
#   PROFILE="a"     tools/chip_calls/pr34_profile.py on that seed (the step by scope, the loop's trace, both forms)
#   C=<dir>         run from that checkout (.bench_archive: git archive $(git write-tree))
# As sent, call 1:  chiprun --timeout 2700 -- env PARENT=1 TRACE1_SEEDS=2147683001 SEEDS="2147683002 2147683003" \
#           FAULTS="2147683011 2147683012" PROGRAM=1 PROFILE=2147683021 sh tools/chip_calls/pr34_cell.sh
# call 2, one command: env TRACE1_SEEDS=2147684001 SEEDS="2147684002 ... 2147684007" FAULTS="2147683013 2147683014" FAULT_ONLY=int8
#           PROGRAM=1 T=c34b sh tools/chip_calls/pr34_cell.sh; python3 tools/chip_calls/pr34_rounding.py 2147686101;
#           env W="qwen3next_score_stream axk1_score_stream" PAIRS=1 T=c34p SEED0=2147643000 sh tools/chip_calls/pr33_pairs.sh;
#           python3 benchmarks/run.py --workload inceptionv3_featurize_stream --seed 2147653001 --seconds 30 --trace 0
# calls 3 and 4 (the final trees, from .bench_archive): tools/chip_calls/pr34_final.sh, whose header quotes both
# S=2 R=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic file's rehearsal sizes.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"
W=${W:-ouro_score_stream}; S=${S:-30}; R=${R:-0}; T=${T:-c34}; C=${C:-.}
show() { grep -E "^(setup|pass|window)" "$OUT/$1.out" | cut -c1-200 | tail -n 9; grep -E "^compared|^correct" "$OUT/$1.err"; tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: v['value'] for k,v in r['metrics'].items()}, r['correct'], r['failed'], r['device']); b=r.get('breakdown',{}); print(b.get('device_ops')); print(b.get('idle_gaps'))"; }
run() {  # run <tag> <seed> <trace>
  t0=$(date +%s)
  ( cd "$C" && python3 benchmarks/run.py --workload $W --seed $2 --seconds $S --trace $3 --rehearsal $R > "$OUT/$1.out" 2> "$OUT/$1.err"; echo "$1 rc=$? wall=$(( $(date +%s) - t0 )) s" )
  show $1; tail -n 4 "$OUT/$1.err" | cut -c1-400
}
if [ -n "$PARENT" ]; then
  t0=$(date +%s)
  ( cd .bench_parent && python3 benchmarks/run.py --workload $W --seed 2147683000 --seconds $S --trace 0 --rehearsal $R > "$OUT/${T}_parent.out" 2> "$OUT/${T}_parent.err"; echo "parent rc=$? wall=$(( $(date +%s) - t0 )) s" )
  tail -n 3 "$OUT/${T}_parent.err" | cut -c1-300
fi
for seed in $TRACE1_SEEDS; do run ${T}_${seed}_t1 $seed 1; done
for seed in $SEEDS; do run ${T}_${seed}_t${TRACE:-0} $seed ${TRACE:-0}; done
if [ -n "$FAULTS" ]; then
  ( cd "$C" && python3 tools/chip_calls/pr34_faults.py --seeds $(echo $FAULTS | tr ' ' ',') --rows ${FAULT_ROWS:-2} --only ${FAULT_ONLY:-int8,three_passes,norm_outside_loop} --rehearsal $R > "$OUT/${T}_faults.out" 2> "$OUT/${T}_faults.err"; echo "faults rc=$?" )
  cut -c1-700 "$OUT/${T}_faults.out"; tail -n 3 "$OUT/${T}_faults.err" | cut -c1-300
fi
if [ -n "$PROFILE" ]; then
  ( cd "$C" && python3 tools/chip_calls/pr34_profile.py $PROFILE > "$OUT/${T}_profile.out" 2> "$OUT/${T}_profile.err"; echo "profile rc=$?" )
  cut -c1-200 "$OUT/${T}_profile.out"; tail -n 3 "$OUT/${T}_profile.err" | cut -c1-300
fi
