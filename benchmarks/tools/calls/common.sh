# shared by the call scripts: run from the archive checkout, bring the result lines back
set -e
OUT=$PWD/chiprun_out
mkdir -p "$OUT"
cd .bench_archive
S=30; R=0; ROWS=320
if [ -n "$REHEARSAL" ]; then S=1; R=1; ROWS=4; fi
sets() {  # sets <workload> <seeds> <tag> <trace>
  python3 benchmarks/tools/sets.py --workload "$1" --seconds $S --seeds "$2" --tag "$3" --trace "$4" --rehearsal $R | cut -c1-1500
  cp chiprun_out/"$3".jsonl "$OUT"/
}
