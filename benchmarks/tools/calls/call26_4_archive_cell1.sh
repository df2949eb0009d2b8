#!/bin/sh
# PR 26, call 4, one chip, from .bench_archive (git archive of the final tree's write-tree): cell 1
# with run.py, with traced.py, and with run.py while the program's tracer and compile log are armed
# through their environment switches and no profiler ever starts (what armed spans alone cost).
# Call 5, four chips, was this script too: chiprun --chips 4 -- env W=inceptionv3_featurize_stream_x4
# SEEDS=2147484961 NO_TRACED=1 sh benchmarks/tools/calls/call26_4_archive_cell1.sh
OUT=$PWD/chiprun_out; mkdir -p "$OUT"; cd .bench_archive
W=${W:-inceptionv3_featurize_stream}; S=${SEEDS:-"2147483951"}
show() { grep -E "^(setup|pass|window|program|slow|  )" "$OUT/$1.out" | cut -c1-150; tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],3) for k,v in r['metrics'].items()}, r['correct'], r['failed'], r.get('breakdown',{}).get('boundary_parts'))"; }
for s in $S; do
  python3 benchmarks/run.py --workload $W --seed $s --seconds 30 --trace 0 > "$OUT/c26f_plain_$s.out" 2> "$OUT/c26f_plain_$s.err"; echo "plain $s rc=$?"; show c26f_plain_$s
  SPARKDL_TPU_TRACE=1 SPARKDL_TPU_COMPILE_LOG=1 python3 benchmarks/run.py --workload $W --seed $s --seconds 30 --trace 0 > "$OUT/c26f_armed_$s.out" 2> "$OUT/c26f_armed_$s.err"; echo "armed $s rc=$?"; show c26f_armed_$s
  if [ -z "$NO_TRACED" ]; then
    python3 benchmarks/traced.py --workload $W --seed $s --seconds 30 > "$OUT/c26f_traced_$s.out" 2> "$OUT/c26f_traced_$s.err"; echo "traced $s rc=$?"; show c26f_traced_$s
  fi
  SPARKDL_TPU_TRACE=1 SPARKDL_TPU_COMPILE_LOG=1 python3 benchmarks/run.py --workload $W --seed $s --seconds 30 --trace 0 > "$OUT/c26f_armed2_$s.out" 2> "$OUT/c26f_armed2_$s.err"; echo "armed2 $s rc=$?"; show c26f_armed2_$s
  python3 benchmarks/run.py --workload $W --seed $s --seconds 30 --trace 0 > "$OUT/c26f_plain2_$s.out" 2> "$OUT/c26f_plain2_$s.err"; echo "plain2 $s rc=$?"; show c26f_plain2_$s
done
