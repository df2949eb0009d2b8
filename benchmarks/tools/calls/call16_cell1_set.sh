#!/bin/sh
# call 16, one chip: cell 1 once more on the seeds of the sets, on another machine than call 14's,
# whose set spread four times wider than the two before it; and a third traced run of the final tree.
. benchmarks/tools/calls/common.sh
W=inceptionv3_featurize_stream
sets $W 2147483701,2147483702,2147483703,2147483704,2147483705,2147483706 c16_set4 0
sets $W 2147483813 c16_traced 1
python3 - <<PY
import json
for line in open("chiprun_out/c16_set4.jsonl"):
    r = json.loads(line)
    print(r["seed"], *[l[:44] for l in r["earlier_lines"] if l.startswith(("pass", "window"))], sep=" | ")
PY
