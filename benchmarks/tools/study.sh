#!/bin/sh
# the window study: one cell, the same seeds at each window length
W=${1:?workload}; SEEDS=${2:?seeds}; shift 2
for s in "$@"; do
  python3 benchmarks/tools/sets.py --workload $W --seconds $s --seeds $SEEDS --tag study_${W}_$s | cut -c1-700
done
