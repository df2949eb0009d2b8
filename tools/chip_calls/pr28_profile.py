"""PR 28's look inside one step of the new cell's program, outside the benchmark:
the configuration's model function over seeded weights, a few steps under the
profiler, and the device time by scope and by instruction (the compile log's
instruction-to-scope map over the trace's ``XLA Ops`` events).

    chiprun -- python3 tools/chip_calls/pr28_profile.py [steps]
"""
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from sparkdl_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import lm_weights, program_lm, tracing  # noqa: E402
from sparkdl_tpu.obs import compile_log  # noqa: E402

steps = int(sys.argv[1]) if len(sys.argv) > 1 else 3
config = json.load(open(os.path.join(ROOT, "benchmarks/configs/qwen3next_80b_a3b_ep4.json")))
t = time.perf_counter()
weights = lm_weights.make_weights(config, 2147480000)
jax.block_until_ready(weights)  # sparkdl-lint: allow[H1] -- a measure tool: the time of the draws is what it reads
print(f"weights {time.perf_counter() - t:.1f} s", flush=True)
compile_log().arm()
mf = program_lm.model_function(config, weights, 8192, routing_stats=True)
tokens = lm_weights.token_rows(7, 2, 8192, config["vocab_size"], 1.0)
t = time.perf_counter()
out = mf({"tokens": tokens})
jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the compile's time is what it reads
print(f"first call {time.perf_counter() - t:.1f} s", flush=True)
scopes = max((e.scopes for e in compile_log().events() if e.scopes), key=len)
log_dir = os.path.join(ROOT, ".bench_trace")
jax.profiler.start_trace(log_dir)
t = time.perf_counter()
for _ in range(steps):
    out = mf({"tokens": tokens})
jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the traced steps end here
print(f"{steps} steps {time.perf_counter() - t:.3f} s", flush=True)
jax.profiler.stop_trace()
data = jax.profiler.ProfileData.from_file(tracing.find_trace_file(log_dir))
seconds: dict = {}
for plane in data.planes:
    if not plane.name.startswith("/device:TPU:"):
        continue
    for line in plane.lines:
        if line.name == "XLA Ops":
            for e in line.events:
                m = re.match(r"^%?([\w.\-]+) = ", e.name)
                key = m.group(1) if m else e.name
                seconds[key] = seconds.get(key, 0.0) + e.duration_ns * 1e-9 / steps
total = sum(seconds.values())
print(f"device time a step {total * 1e3:.1f} ms")
by_scope: dict = {}
for name, s in seconds.items():
    path = scopes.get(name, "(no scope)").split("/")
    kind = "/".join(re.sub(r"_\d+$", "", p) for p in path[:3])
    by_scope[kind] = by_scope.get(kind, 0.0) + s
for kind, s in sorted(by_scope.items(), key=lambda kv: -kv[1])[:25]:
    print(f"  {s * 1e3:8.2f} ms  {kind}")
print("instructions of layers 0 (delta rule) and 3 (attention), the head and the rest, 0.2 ms and more:")
for name, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
    path = scopes.get(name, "")
    if s >= 0.2e-3 and not re.search(r"_[124567]\b", path):
        print(f"  {s * 1e3:8.3f} ms  {name:32s} {path[:100]}")
