#!/bin/sh
# PR 36's chip call: what set-up is made of, in each cell of W, on a first run and on a warm one, beside the
# parent. The change is C (the tree this runs from, or .bench_archive: git archive $(git write-tree)); the parent
# is .bench_parent (git archive of c1fa0cf with this tree's BENCHMARK.json and benchmarks/ laid over it, as the
# driver lays them). For each cell, in this order, every run with a seed of its own but the two of the pair:
#   c_first  the change, `--trace 1`, its .jax_cache emptied first: the first run of a checkout. Where the machine sets
#            JAX_COMPILATION_CACHE_DIR that directory is the one in use and is left alone: the run is a first one only
#            if it prints setup.cache_miss_share 100 (PR 36's did: a Pallas program's key holds the call stack, and
#            this entry script was new to it; the machine's cache had never held cell 1's programs)
#   p_1      the parent, `--trace 0`: its own first run (a Pallas program's cache key holds the source path)
#   c_2 p_2  the pair, `--trace 0`, one seed, both warm: rows_per_s and setup_s, change against parent
#   c_warm   the change, `--trace 1`, warm: the five setup.* metrics of a warm run
# and for the cells of ARMED (default: cell 4) one more pair on one seed, `--trace 0`: c_plain against c_armed,
# the span tracer and the compile log armed through SPARKDL_TPU_TRACE=1 SPARKDL_TPU_COMPILE_LOG=1.
# The change's traced and armed runs go through pr36_setup.py, which prints CompileLog.phases() and the counters
# at the end of set-up, before the window and the reference. Then, with PROBE=1, pr36_probe.py (does device_put
# return before the bytes land; a second device-batch size forced after a warm pass). Every run's output is
# chiprun_out/<tag>.{out,err}; every number is printed as the run gave it.
#   one chip:    chiprun --timeout 3500 -- env C=.bench_archive PROBE=1 sh tools/chip_calls/pr36_setup.sh
#   four chips:  chiprun --chips 4 --timeout 1500 -- env C=.bench_archive W=inceptionv3_featurize_stream_x4 ARMED= T=c36x4 sh tools/chip_calls/pr36_setup.sh
# S=1 R=1 JAX_PLATFORMS=cpu W=inceptionv3_featurize_stream rehearses it on the CPU at the rehearsal sizes.
OUT=$PWD/chiprun_out; mkdir -p "$OUT"; HERE=$PWD
W=${W:-inceptionv3_featurize_stream qwen3next_score_stream axk1_score_stream ouro_score_stream}
ARMED=${ARMED-qwen3next_score_stream}
S=${S:-30}; R=${R:-0}; C=${C:-.}; T=${T:-c36}; B=${SEED0:-2147736000}
show() {
  grep -E "^setup" "$OUT/$1.out" | cut -c1-200; grep -E "^correct" "$OUT/$1.err"
  tail -n 1 "$OUT/$1.out" | python3 -c "
import json, sys
r = json.loads(sys.stdin.read())
m = {k: v['value'] for k, v in r['metrics'].items()}
print('$1', {k: v for k, v in m.items() if 'setup' in k or 'rows_per_s' in k or 'step_ms' in k or 'idle' in k},
      'correct', r['correct'], 'failed', r['failed'], 'peak', r['device'].get('memory_peak_bytes'), 'metrics', len(m))"
  grep -E "^(setup_end|process_end) " "$OUT/$1.err" | python3 -c "
import json, sys
for line in sys.stdin:
    what, _, body = line.partition(' ')
    d = json.loads(body)
    c = d['counters']
    print('  ', what, {k: v for k, v in c.items() if k.startswith(('compile.', 'ship.params')) and v})
    if what == 'setup_end' and isinstance(d.get('phases'), dict):
        marks = dict(d['marks'])
        known = sum(c.get(k, 0.0) for k in ('compile.trace_seconds', 'compile.lower_seconds',
                                             'compile.backend_seconds', 'ship.params_place_seconds'))
        whole = marks.get('program', 0.0) + marks.get('warm pass', 0.0)
        print('   program + warm pass %.3f s, trace + lower + backend + place %.3f s, remainder %.3f s'
              % (whole, known, whole - known))
        rows = sorted(d['phases'].items(), key=lambda kv: -(kv[1]['trace_s'] + kv[1]['lower_s'] + kv[1]['backend_s']))
        for name, e in rows[:6]:
            print('   phases', name, {k: (round(v, 4) if isinstance(v, float) else v) for k, v in e.items() if v})
        rest = rows[6:]
        print('   phases: %d more names, %.3f s' % (len(rest), sum(e['trace_s'] + e['lower_s'] + e['backend_s'] for _, e in rest)))"
}
run() {  # run <dir> <tag> <workload> <seed> <trace> <entry: run.py, or the wrapper before it> [environment]
  t0=$(date +%s)
  ( cd "$1" && env $7 python3 $6 --workload $3 --seed $4 --seconds $S --trace $5 --rehearsal $R > "$OUT/$2.out" 2> "$OUT/$2.err"; echo "$2 rc=$? wall=$(( $(date +%s) - t0 )) s" )
  show $2
}
PLAIN=benchmarks/run.py; HEARD="$HERE/tools/chip_calls/pr36_setup.py benchmarks/run.py"
n=0
for w in $W; do
  n=$((n+1)); s=$((B + 100 * n))
  rm -rf "$C/.jax_cache"
  run $C ${T}_${n}_c_first $w $((s + 1)) 1 "$HEARD"
  run .bench_parent ${T}_${n}_p_1 $w $((s + 2)) 0 $PLAIN
  run $C ${T}_${n}_c_2 $w $((s + 3)) 0 $PLAIN
  run .bench_parent ${T}_${n}_p_2 $w $((s + 3)) 0 $PLAIN
  run $C ${T}_${n}_c_warm $w $((s + 4)) 1 "$HEARD"
  case " $ARMED " in *" $w "*)
    run $C ${T}_${n}_c_plain $w $((s + 5)) 0 $PLAIN
    run $C ${T}_${n}_c_armed $w $((s + 5)) 0 "$HEARD" "SPARKDL_TPU_TRACE=1 SPARKDL_TPU_COMPILE_LOG=1";;
  esac
done
if [ -n "$PROBE" ]; then
  ( cd $C && env ALONE="${ALONE-qwen3next_score_stream axk1_score_stream ouro_score_stream}" python3 "$HERE/tools/chip_calls/pr36_probe.py" > "$OUT/${T}_probe.out" 2> "$OUT/${T}_probe.err"; echo "probe rc=$?" )
  grep -v "^E[0-9]" "$OUT/${T}_probe.out"
fi
