#!/bin/sh
# PR 28's chip calls for the new cell. Each run of run.py goes to chiprun_out/<tag>.{out,err};
# the last line of .out is the result. As sent:
#   call 1 (first look):  chiprun --timeout 1500 -- env SEEDS="2147490001" TRACE=1 sh tools/chip_calls/pr28_cell.sh
#   later calls: see PERF.md section 6, PR 28
OUT=$PWD/chiprun_out; mkdir -p "$OUT"
W=${W:-qwen3next_score_stream}; S=${S:-30}; T=${T:-c28}; C=${C:-.}
show() { grep -E "^(setup|pass|window)" "$OUT/$1.out" | cut -c1-170 | tail -n 14; grep -E "^compared|^correct" "$OUT/$1.err"; tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],4) for k,v in r['metrics'].items()}, r['correct'], r['failed'], r['device']); b=r.get('breakdown',{}); print(b.get('device_ops')); print(b.get('idle_gaps'))"; }
if [ -n "$PROFILE" ]; then python3 tools/chip_calls/pr28_profile.py 3 2>&1 | grep -v Warn | cut -c1-170; fi
for seed in $SEEDS; do
  tag=${T}_${seed}_t${TRACE:-0}
  ( cd "$C" && python3 benchmarks/run.py --workload $W --seed $seed --seconds $S --trace ${TRACE:-0} > "$OUT/$tag.out" 2> "$OUT/$tag.err"; echo "$tag rc=$?" )
  show $tag; tail -n 5 "$OUT/$tag.err" | cut -c1-400
done
for seed in $TRACE1_SEEDS; do
  tag=${T}_${seed}_t1
  ( cd "$C" && python3 benchmarks/run.py --workload $W --seed $seed --seconds $S --trace 1 > "$OUT/$tag.out" 2> "$OUT/$tag.err"; echo "$tag rc=$?" )
  show $tag
done
if [ -n "$TRACED" ]; then
  ( cd "$C" && python3 benchmarks/traced.py --workload $W --seed $TRACED --seconds $S > "$OUT/${T}_traced.out" 2> "$OUT/${T}_traced.err"; echo "traced rc=$?" )
  show ${T}_traced; tail -n 1 "$OUT/${T}_traced.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print(r['breakdown'].get('device_blocks'))"; tail -n 5 "$OUT/${T}_traced.err" | cut -c1-400
fi
if [ -n "$CONTROL" ]; then
  python3 benchmarks/tools/control_lm.py --config benchmarks/configs/qwen3next_80b_a3b_ep4.json --traffic benchmarks/traffic/tokens_stream.json --seeds $CONTROL --rows ${CONTROL_ROWS:-4} > "$OUT/${T}_control.out" 2> "$OUT/${T}_control.err"; echo "control rc=$?"
  cat "$OUT/${T}_control.out" | cut -c1-600; tail -n 3 "$OUT/${T}_control.err" | cut -c1-300
fi
