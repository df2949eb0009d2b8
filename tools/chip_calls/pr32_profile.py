"""PR 32's look inside cell 5's step, outside the benchmark: for each seed the
configuration's model function over seeded weights and 16 rows of the cell's traffic,
every step of 2 rows timed alone beside what the router gave the held experts in it
(assignments held, tiles of the grouped buffer in use), so that a step's time can be
set against its routing; then, unless STEPS_ONLY is set, for the last seed two steps under
the profiler and the device time by scope and by instruction (`pr28_profile.py` for this
configuration).

    chiprun -- [env STEPS_ONLY=1] python3 tools/chip_calls/pr32_profile.py [seed ...]
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

from benchmarks import lm_weights, tracing  # noqa: E402
from benchmarks.drivers.token_stream_routed import model_function  # noqa: E402
from sparkdl_tpu.obs import compile_log  # noqa: E402
from sparkdl_tpu.ops import moe  # noqa: E402

seeds = [int(s) for s in sys.argv[1:]] or [2147586001, 2147586002, 2147586003]
config = json.load(open(os.path.join(ROOT, "benchmarks/configs/axk1_ep16.json")))
tile = moe.row_tile(config["hidden_size"], config["moe_intermediate_size"], 2)
compile_log().arm()
for seed in seeds:
    t = time.perf_counter()
    weights = lm_weights.make_weights(config, seed)
    mf = model_function(config, weights, 8192, routing_stats=True)
    weights = None
    tokens = lm_weights.token_rows(seed, 16, 8192, config["vocab_size"], 1.0)
    out = mf({"tokens": tokens[:2]})
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: set-up ends here
    print(f"seed {seed}: weights and first call {time.perf_counter() - t:.1f} s", flush=True)
    for lo in range(0, 16, 2):
        t = time.perf_counter()
        out = mf({"tokens": tokens[lo:lo + 2]})
        jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: one step's time
        ms = (time.perf_counter() - t) * 1e3
        counts = np.asarray(out["routing"])[:, :, 1:].sum(axis=0)  # [routed layers, held]
        tiles = int(np.ceil(counts / tile).sum())
        print(f"  step {lo // 2}: {ms:8.2f} ms  held {int(counts.sum()):6d} "
              f"({100 * counts.sum() / (2 * 8192 * 8 * len(counts)):.2f}%)  tiles {tiles:4d}  "
              f"by layer {counts.sum(axis=1).tolist()}  largest expert {int(counts.max())}", flush=True)

if os.environ.get("STEPS_ONLY"):
    sys.exit(0)
scopes = max((e.scopes for e in compile_log().events() if e.scopes), key=len)
log_dir = os.path.join(ROOT, ".bench_trace")
steps = 2
jax.profiler.start_trace(log_dir)
for lo in range(0, 2 * steps, 2):
    out = mf({"tokens": tokens[lo:lo + 2]})
jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the traced steps end here
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
print(f"device time a step {sum(seconds.values()) * 1e3:.1f} ms")
by_scope: dict = {}
for name, s in seconds.items():
    path = scopes.get(name, "(no scope)").split("/")
    kind = "/".join(re.sub(r"_\d+$", "", p) for p in path[:3])
    by_scope[kind] = by_scope.get(kind, 0.0) + s
for kind, s in sorted(by_scope.items(), key=lambda kv: -kv[1])[:25]:
    print(f"  {s * 1e3:8.2f} ms  {kind}")
print("instructions of layers 0 (dense) and 1 (routed), the head and the rest, 0.2 ms and more:")
for name, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
    path = scopes.get(name, "")
    if s >= 0.2e-3 and not re.search(r"_[2345]\b", path):
        print(f"  {s * 1e3:8.3f} ms  {name:36s} {path[:110]}")
