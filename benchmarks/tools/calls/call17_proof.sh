#!/bin/sh
# call 17, one chip: the tree as committed (PERF.md and CHANGES.md written), from the archive checkout:
# one run of cell 1 on a seed it has not had.
. benchmarks/tools/calls/common.sh
sets inceptionv3_featurize_stream 3000000201 c17_proof 0
