#!/bin/sh
# PR 26, call 2, one chip: cell 1. Parent (.bench_parent, git archive of 34c4aa9) against the change
# with tracing off, the same seeds on both sides; the change's traced runs (traced.py: the program's
# spans) on the same seeds, for what tracing on costs; run.py --trace 1 on the parent with this PR's
# benchmark files laid over it, as the driver's check does; and how long one device batch takes to upload.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"
W=inceptionv3_featurize_stream
run() {  # run <dir> <tag> <seed> <trace>
  ( cd "$1" && python3 benchmarks/run.py --workload $W --seed $3 --seconds 30 --trace $4 > "$OUT/$2.out" 2> "$OUT/$2.err"; echo "$2 rc=$?" )
  grep -E "^(setup|pass|window)" "$OUT/$2.out" | cut -c1-160
  tail -n 1 "$OUT/$2.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],3) for k,v in r['metrics'].items()}, r['correct'], r['failed'])"
}
traced() {  # traced <tag> <seed>
  python3 benchmarks/traced.py --workload $W --seed $2 --seconds 30 --keep "$OUT/$1.spans.json" > "$OUT/$1.out" 2> "$OUT/$1.err"; echo "$1 rc=$?"
  grep -E "^(setup|pass|window|program|slow|  )" "$OUT/$1.out" | cut -c1-160
  tail -n 1 "$OUT/$1.out" | python3 -c "import json,sys; r=json.loads(sys.stdin.read()); print({k: round(v['value'],3) for k,v in r['metrics'].items()}, r['correct'], r['failed']); print(r['breakdown']['boundary_parts']); print(r['breakdown']['device_blocks'])"
}
run .bench_parent c26_p_931 2147483931 0
run . c26_c_931 2147483931 0
traced c26_t_931 2147483931
run . c26_c_932 2147483932 0
run .bench_parent c26_p_932 2147483932 0
traced c26_t_932 2147483932
run .bench_parent c26_p_933 2147483933 0
run . c26_c_933 2147483933 0
traced c26_t_933 2147483933
rm -rf .bench_overlay && cp -r .bench_parent .bench_overlay && cp -r BENCHMARK.json benchmarks .bench_overlay/
run .bench_overlay c26_overlay_934 2147483934 1
python3 - <<'PY'
# one device batch of cell 1 (1,024 x 299 x 299 x 3 uint8, 275 MB) from numpy to the device:
# alone, then three started together, as the first three dispatches of a partition start theirs
import time, numpy as np, jax
x = [np.random.default_rng(i).integers(0, 255, (1024, 299, 299, 3), dtype=np.uint8) for i in range(3)]
jax.device_put(x[0]).block_until_ready()
for n in (1, 1, 3, 3, 2):
    t = time.perf_counter(); ys = [jax.device_put(a) for a in x[:n]]; t_put = time.perf_counter() - t
    done = []
    for y in ys:
        y.block_until_ready(); done.append(round((time.perf_counter() - t) * 1e3, 1))
    print(f"upload of {n} batches started together: put calls {t_put*1e3:.1f} ms, each ready after {done} ms", flush=True)
PY
