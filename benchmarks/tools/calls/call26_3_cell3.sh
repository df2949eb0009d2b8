#!/bin/sh
# PR 26, call 3, four chips: cell 3. Parent (.bench_parent) against the change with tracing off on
# two seeds, the change alone on a third, and the change's traced runs (traced.py) on all three.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"
W=inceptionv3_featurize_stream_x4
show() { grep -E "^(setup|pass|window|program|slow|  )" "$OUT/$1.out" | cut -c1-160; }
run() {  # run <dir> <tag> <seed>
  ( cd "$1" && python3 benchmarks/run.py --workload $W --seed $3 --seconds 30 --trace 0 > "$OUT/$2.out" 2> "$OUT/$2.err"; echo "$2 rc=$?" )
  show $2
  tail -n 1 "$OUT/$2.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],3) for k,v in r['metrics'].items()}, r['correct'], r['failed'])"
}
traced() {  # traced <tag> <seed>
  python3 benchmarks/traced.py --workload $W --seed $2 --seconds 30 --keep "$OUT/$1.spans.json" > "$OUT/$1.out" 2> "$OUT/$1.err"; echo "$1 rc=$?"
  show $1; tail -n 3 "$OUT/$1.err"
  tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],3) for k,v in r['metrics'].items()}, r['correct'], r['failed']); print(r['breakdown']['boundary_parts']); print(r['breakdown']['device_blocks']); print(r['breakdown']['idle_gaps'])"
}
run .bench_parent c26x_p_941 2147484941
run . c26x_c_941 2147484941
traced c26x_t_941 2147484941
run . c26x_c_942 2147484942
run .bench_parent c26x_p_942 2147484942
traced c26x_t_942 2147484942
run . c26x_c_943 2147484943
traced c26x_t_943 2147484943
