"""``benchmarks/run.py`` of the checkout in the working directory, with the driver's
``observed`` readings (all but the scope map) printed on standard error as one line,
``observed: {...}``, before the result: what the per-layer readers are given, so that two
checkouts can be held to the same counters. Arguments are ``run.py``'s.

    cd <checkout> && python3 <this file> --workload W --seed N --seconds 30 --trace 1
"""

import json
import os
import sys

sys.path.insert(0, os.getcwd())
from benchmarks import run as bench_run  # noqa: E402  (its clock starts here)
from benchmarks import harness  # noqa: E402

_init = harness.Outcome.__init__


def _printing_init(self, *args, **kwargs):
    _init(self, *args, **kwargs)
    shown = {k: v for k, v in self.observed.items() if k != "program.scopes"}
    print("observed: " + json.dumps(shown), file=sys.stderr, flush=True)


harness.Outcome.__init__ = _printing_init
sys.exit(bench_run.main(sys.argv[1:]))
