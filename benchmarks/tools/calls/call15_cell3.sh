#!/bin/sh
# call 15, four chips: cell 3 on the final tree, from the archive checkout: one run and one traced
# run, whose pass lines give runner.run's seconds and the share outside it for every pass.
. benchmarks/tools/calls/common.sh
W=inceptionv3_featurize_stream_x4
sets $W 2147484711 c15_run 0
sets $W 2147484811 c15_traced 1
python3 - <<PY
import json
for tag in ("c15_run", "c15_traced"):
    for line in open("chiprun_out/%s.jsonl" % tag):
        r = json.loads(line)
        print(tag, r["seed"], *[l for l in r["earlier_lines"] if l.startswith(("pass", "window", "setup"))], sep="\n  ")
PY
