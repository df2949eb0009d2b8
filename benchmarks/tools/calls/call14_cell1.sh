#!/bin/sh
# call 14, one chip: cell 1 on the final tree, from the archive checkout: a first run, a set of six
# on the seeds of the earlier sets, two traced runs, the int8 control on three seeds at a run's
# number of rows, and the refusal in a directory that holds the benchmark alone.
. benchmarks/tools/calls/common.sh
W=inceptionv3_featurize_stream
sets $W 3000000101 c14_first 0
sets $W 2147483701,2147483702,2147483703,2147483704,2147483705,2147483706 c14_set3 0
sets $W 2147483811,2147483812 c14_traced 1
if python3 benchmarks/tools/control.py --config benchmarks/configs/inceptionv3_featurize.json \
  --seeds 2147483701,2147483702,2147483703 --rows $ROWS --rehearsal $R > "$OUT"/c14_control.jsonl
then echo "control: exit 0, not correct on every seed"
else echo "control: exit $?, it came out correct on a seed, or failed"
fi
cat "$OUT"/c14_control.jsonl
mkdir -p ../.bench_bare && cp -r BENCHMARK.json benchmarks ../.bench_bare/ && cd ../.bench_bare
if python3 benchmarks/run.py --workload $W --seed 5 --seconds 1 --trace 0 > bare.out 2> bare.err; then
  echo "bare directory: exit 0, which is wrong"; exit 1
else
  echo "bare directory: exit $?, $(wc -l < bare.out) lines on standard output; $(tail -n 1 bare.err)"
fi
