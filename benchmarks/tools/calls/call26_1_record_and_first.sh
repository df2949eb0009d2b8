set -x
python3 benchmarks/tests/record_boundary.py 2>&1 | grep -v hugepages | tail -40
python3 benchmarks/traced.py --workload inceptionv3_featurize_stream --seed 2147483921 --seconds 30 > chiprun_out/t26_first.out 2> chiprun_out/t26_first.err
echo rc=$?
tail -5 chiprun_out/t26_first.err
grep -v '^{' chiprun_out/t26_first.out
tail -1 chiprun_out/t26_first.out | python3 -c "
import json,sys
r=json.loads(sys.stdin.read())
print(json.dumps(r['metrics'],indent=0))
print(json.dumps(r['breakdown']['boundary_parts']))
print(json.dumps(r['breakdown']['device_blocks']))
print(json.dumps(r['breakdown']['idle_gaps']))
print(r['device'], r['correct'])
"
