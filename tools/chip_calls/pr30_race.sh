#!/bin/sh
# PR 30's step 0: the race the parent's switches allow, run at the parent (.bench_parent: git archive of
# 144364d) before any edit of the ship layer. Each alternative runs twice, every round beside a default run
# of the same seed on the same machine, the second round in the opposite order; tracing off; every run under
# with_counters.py, which prints the registry's ship.* counters (ring hits, donations, the carry). Each run
# goes to chiprun_out/<tag>.{out,err}; the last line of .out is the result. As sent:
#   call 1, cells 1 and 4:  chiprun --timeout 3400 -- sh -c 'sh tools/chip_calls/pr30_race.sh;
#       env W=qwen3next_score_stream T=r30l SEED0=2147511000 ALTS="RUNNER_STRATEGY=host_async RUNNER_STRATEGY=immediate" \
#       sh tools/chip_calls/pr30_race.sh'
#   call 2, cell 3:  chiprun --chips 4 --timeout 1800 -- env W=inceptionv3_featurize_stream_x4 T=r30x SEED0=2147512000 \
#       ALTS="RUNNER_STRATEGY=host_async RUNNER_STRATEGY=prefetch TRANSFER_INTERLEAVE=4 RUNNER_STRATEGY=prefetch,TRANSFER_INTERLEAVE=4" \
#       ALTS2="RUNNER_STRATEGY=prefetch,TRANSFER_INTERLEAVE=4 RUNNER_STRATEGY=prefetch RUNNER_STRATEGY=host_async" sh tools/chip_calls/pr30_race.sh
#     (TRANSFER_INTERLEAVE alone once: by the code it engages nothing unless the strategy is prefetch or a ring is on, so the
#     pair that gives the interleave pool its chance is prefetch with it against prefetch without)
# An alternative is NAME=value of one SPARKDL_TPU_ switch, or several joined by commas.
# After the deletion, the final tree (git archive $(git write-tree) | tar -x -C .bench_archive) against the parent, with PR 27's and 29's scripts:
#   call 3, cells 1 and 4:  chiprun --timeout 2400 -- sh -c 'env C=.bench_archive T=c30f SEED0=2147513000 SHORT=1 TRACE1=1 sh tools/chip_calls/pr27_pairs.sh;
#       env W=qwen3next_score_stream C=.bench_archive PAIRS=1 SEED0=2147514000 T=c30l sh tools/chip_calls/pr29_pairs.sh'
#   call 4, cell 3:  chiprun --chips 4 --timeout 1200 -- env W=inceptionv3_featurize_stream_x4 C=.bench_archive T=c30x SEED0=2147515000 ONE=1 \
#       sh tools/chip_calls/pr27_pairs.sh   (ONE=1 without SHORT=1 runs three pairs and a traced run, not one pair)
# S=1 R=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"; ROOT=$PWD
W=${W:-inceptionv3_featurize_stream}; S=${S:-30}; R=${R:-0}; P=${P:-.bench_parent}; T=${T:-r30}; B=${SEED0:-2147510000}
ALTS=${ALTS:-"RUNNER_STRATEGY=immediate RUNNER_STRATEGY=host_async RUNNER_STRATEGY=prefetch INFEED_RING=4"}
unset SPARKDL_TPU_RUNNER_STRATEGY SPARKDL_TPU_PREFETCH_DEPTH SPARKDL_TPU_INFEED_RING SPARKDL_TPU_TRANSFER_INTERLEAVE
show() { grep -E "^counters" "$OUT/$1.err" | cut -c1-600; tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print('$1', {k: v['value'] for k,v in r['metrics'].items()}, r['correct'], r['failed'], r['device'].get('memory_peak_bytes'))"; }
tag() { echo "$1" | sed 's/[A-Z_]*=//g; s/,/+/g'; }
run() {  # run <tag> <seed> [<alternative>]
  ( cd "$P" && env $(echo "$3" | tr ',' '\n' | sed '/./s/^/SPARKDL_TPU_/') python3 "$ROOT/tools/chip_calls/with_counters.py" benchmarks/run.py --workload $W --seed $2 --seconds $S --trace 0 --rehearsal $R > "$OUT/$1.out" 2> "$OUT/$1.err"; echo "$1 ${3:-default} rc=$?" )
  show $1
}
run ${T}_default_1 $((B+1))
for a in $ALTS; do run ${T}_$(tag $a)_1 $((B+1)) $a; done
REV=; for a in $ALTS; do REV="$a $REV"; done
for a in ${ALTS2:-$REV}; do run ${T}_$(tag $a)_2 $((B+2)) $a; done
run ${T}_default_2 $((B+2))
