#!/bin/sh
# PR 32's chip calls for the cell axk1_score_stream: pr28_cell.sh with the configuration and the traffic named, and
# first the parent's try of the new cell. Each run goes to chiprun_out/<tag>.{out,err}; the last line of .out is the result.
#   PARENT=1        .bench_parent (git archive of the parent, this PR's BENCHMARK.json and benchmarks/ laid over it) is
#                   asked for the cell first: it has to end at once, with another exit code than 0
#   SEEDS="a b"     one run a seed, TRACE=0|1;  TRACE1_SEEDS="c"  further --trace 1 runs;  TRACED=<seed>  a traced.py run
#   CONTROL="a b"   the int8 control on those seeds, CONTROL_ROWS rows each (tools/control_lm.py)
#   C=<dir>         run from that checkout (.bench_archive: git archive $(git write-tree))
# As sent:  chiprun --timeout 3000 -- env PARENT=1 TRACE1_SEEDS=2147583001 SEEDS=2147583002 CONTROL="2147583003 2147583004" \
#           CONTROL_ROWS=2 sh tools/chip_calls/pr32_cell.sh
# S=2 R=1 JAX_PLATFORMS=cpu rehearses it on the CPU at the traffic file's rehearsal sizes.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"
W=${W:-axk1_score_stream}; S=${S:-30}; R=${R:-0}; T=${T:-c32}; C=${C:-.}
CONFIG=${CONFIG:-benchmarks/configs/axk1_ep16.json}; TRAFFIC=${TRAFFIC:-benchmarks/traffic/tokens_stream_p16.json}
show() { grep -E "^(setup|pass|window)" "$OUT/$1.out" | cut -c1-170 | tail -n 12; grep -E "^compared|^correct" "$OUT/$1.err"; tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],4) for k,v in r['metrics'].items()}, r['correct'], r['failed'], r['device']); b=r.get('breakdown',{}); print(b.get('device_ops')); print(b.get('idle_gaps'))"; }
run() {  # run <tag> <seed> <trace>
  t0=$(date +%s)
  ( cd "$C" && python3 benchmarks/run.py --workload $W --seed $2 --seconds $S --trace $3 --rehearsal $R > "$OUT/$1.out" 2> "$OUT/$1.err"; echo "$1 rc=$? wall=$(( $(date +%s) - t0 )) s" )
  show $1; tail -n 4 "$OUT/$1.err" | cut -c1-400
}
if [ -n "$PARENT" ]; then
  t0=$(date +%s)
  ( cd .bench_parent && python3 benchmarks/run.py --workload $W --seed 2147583000 --seconds $S --trace 0 --rehearsal $R > "$OUT/${T}_parent.out" 2> "$OUT/${T}_parent.err"; echo "parent rc=$? wall=$(( $(date +%s) - t0 )) s" )
  tail -n 3 "$OUT/${T}_parent.err" | cut -c1-300
fi
for seed in $TRACE1_SEEDS; do run ${T}_${seed}_t1 $seed 1; done
for seed in $SEEDS; do run ${T}_${seed}_t${TRACE:-0} $seed ${TRACE:-0}; done
if [ -n "$TRACED" ]; then
  ( cd "$C" && python3 benchmarks/traced.py --workload $W --seed $TRACED --seconds $S --rehearsal $R > "$OUT/${T}_traced.out" 2> "$OUT/${T}_traced.err"; echo "traced rc=$?" )
  show ${T}_traced; tail -n 1 "$OUT/${T}_traced.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print(r['breakdown'].get('device_blocks'))"; tail -n 4 "$OUT/${T}_traced.err" | cut -c1-400
fi
if [ -n "$CONTROL" ]; then
  python3 benchmarks/tools/control_lm.py --config $CONFIG --traffic $TRAFFIC --seeds $(echo $CONTROL | tr ' ' ',') --rows ${CONTROL_ROWS:-4} --rehearsal $R > "$OUT/${T}_control.out" 2> "$OUT/${T}_control.err"; echo "control rc=$?"
  cut -c1-600 "$OUT/${T}_control.out"; tail -n 3 "$OUT/${T}_control.err" | cut -c1-300
fi
