#!/bin/sh
# PR 27's chip calls. Parent (.bench_parent: git archive of b5a10c7) against the change (the tree
# this runs from), tracing off, one seed for both sides of a pair and the order alternating; between
# them the change's traced runs (benchmarks/traced.py) for the gap's parts, breakdown.idle_gaps and,
# on stderr, how the runs of the whole process began (with_counters.py). As sent:
#   call 1, cell 1:  chiprun --timeout 1800 -- sh tools/chip_calls/pr27_pairs.sh
#   call 2, cell 3:  chiprun --chips 4 --timeout 1800 -- env W=inceptionv3_featurize_stream_x4 \
#                      SEED0=2147486000 T=c27x SHORT=1 sh tools/chip_calls/pr27_pairs.sh
#   call 3, cell 1, the final tree as git would commit it (git archive $(git write-tree) | tar -x -C
#                    .bench_archive):  chiprun --timeout 1800 -- env C=.bench_archive T=c27f \
#                      SEED0=2147487000 SHORT=1 TRACE1=1 sh tools/chip_calls/pr27_pairs.sh
#   call 4, the same after the last edit (a stale carry under contention): ... T=c27g SEED0=2147488000 ONE=1 ...
# S=1 R=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic files' rehearsal sizes.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"; ROOT=$PWD
W=${W:-inceptionv3_featurize_stream}; S=${S:-30}; R=${R:-0}; C=${C:-.}
show() { grep -E "^(setup|pass|window|program|slow|  )" "$OUT/$1.out" | cut -c1-170; grep -E "^counters" "$OUT/$1.err"; tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],3) for k,v in r['metrics'].items()}, r['correct'], r['failed']); b=r.get('breakdown',{}); print(b.get('boundary_parts')); print(b.get('idle_gaps'))"; }
run() {  # run <dir> <tag> <seed> [<trace>]
  ( cd "$1" && python3 "$ROOT/tools/chip_calls/with_counters.py" benchmarks/run.py --workload $W --seed $3 --seconds $S --trace ${4:-0} --rehearsal $R > "$OUT/$2.out" 2> "$OUT/$2.err"; echo "$2 rc=$?" )
  show $2
}
traced() {  # traced <tag> <seed>
  ( cd "$C" && python3 "$ROOT/tools/chip_calls/with_counters.py" benchmarks/traced.py --workload $W --seed $2 --seconds $S --rehearsal $R --keep "$OUT/$1.spans.json" > "$OUT/$1.out" 2> "$OUT/$1.err"; echo "$1 rc=$?" )
  show $1; tail -n 3 "$OUT/$1.err" | cut -c1-300
}
B=${SEED0:-2147485000}; T=${T:-c27}
run .bench_parent ${T}_p_1 $((B+1))
run $C ${T}_c_1 $((B+1))
if [ -z "$ONE" ]; then
traced ${T}_t_1 $((B+11))
run $C ${T}_c_2 $((B+2))
run .bench_parent ${T}_p_2 $((B+2))
fi
if [ -n "$TRACE1" ]; then run $C ${T}_r1 $((B+21)) 1; fi  # the driver's own traced run
if [ -z "$SHORT" ]; then
run .bench_parent ${T}_p_3 $((B+3))
run $C ${T}_c_3 $((B+3))
run $C ${T}_c_4 $((B+4))
run .bench_parent ${T}_p_4 $((B+4))
traced ${T}_t_2 $((B+12))
fi
