#!/bin/sh
# PR 28's last one-chip call: the tree as git would commit it (.bench_archive: git archive $(git write-tree))
# against the parent (.bench_parent: git archive f22d61d). First the new cell from the archive, traced, to
# prove that the committed files are enough; then cell 1 in pairs on one seed each, order alternating
# (pr27_pairs.sh), to show that it has not moved. As sent:
#   chiprun --timeout 2400 -- sh tools/chip_calls/pr28_final.sh
#   chiprun --chips 4 --timeout 1800 -- env W=inceptionv3_featurize_stream_x4 SEED0=2147493000 T=c28x ONE=1 \
#       C=.bench_archive sh tools/chip_calls/pr27_pairs.sh        (cell 3, one pair)
env C=.bench_archive T=c28f TRACE1_SEEDS=2147492001 sh tools/chip_calls/pr28_cell.sh
env C=.bench_archive T=c28p SEED0=2147492100 SHORT=1 sh tools/chip_calls/pr27_pairs.sh
