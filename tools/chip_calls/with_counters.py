"""Run a benchmark entry point (run.py or traced.py) unchanged, then print the ship layer's registry
counters (how the runs of the window began: ship.boundary_*, ship.carry_dropped, ship.inflight*) on
stderr. PR 27's chip calls; since PR 30 every ``ship.*`` key, so that a race shows what engaged."""
import json
import runpy
import sys

script, sys.argv = sys.argv[1], sys.argv[1:]
code = 0
try:
    runpy.run_path(script, run_name="__main__")
except SystemExit as e:
    code = e.code or 0
from sparkdl_tpu.obs import default_registry  # noqa: E402

snap = default_registry().snapshot()
print("counters " + json.dumps({k: v for k, v in sorted(snap.items())
                                if k.startswith("ship.")}),
      file=sys.stderr, flush=True)
sys.exit(code)
