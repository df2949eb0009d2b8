"""PR 34's look inside cell 6's step, outside the benchmark: the configuration's model function
(`drivers/token_stream_looped.model_function`) over seeded weights and 8 rows of the cell's
traffic. (1) The committed program, a loop on the device: the first call (trace, lower,
compile), then every step of 2 rows timed alone. (2) Two steps under the profiler: what the
device plane holds for a program with a loop in it (its lines; the `while` instructions'
events beside their bodies'; the sum of all `XLA Ops` events against the `XLA Modules`
events), the device time by scope and the longest instructions, each with how often it ran.
(3) Unless LOOP_ONLY is set, the same 48 layers written out inside one pass (a throw-away form
that lives here and nowhere in the package: the stacked leaves cut into 48 trees, a Python
`for` in place of the scan over layers, the scan over passes kept), first call and steps,
for ISSUE 34's comparison of the two forms.

    chiprun -- [env LOOP_ONLY=1] python3 tools/chip_calls/pr34_profile.py [seed]
    ROWS=4 REHEARSAL=1 JAX_PLATFORMS=cpu rehearses it at the traffic file's rehearsal sizes.
"""
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from sparkdl_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import lm_weights, tracing  # noqa: E402
from benchmarks.drivers.token_stream_looped import model_function  # noqa: E402
from sparkdl_tpu.models import lm_blocks, ouro  # noqa: E402
from sparkdl_tpu.obs import compile_log  # noqa: E402

seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147686001
config = json.load(open(os.path.join(ROOT, "benchmarks/configs/ouro_2p6b.json")))
traffic = json.load(open(os.path.join(ROOT, "benchmarks/traffic/tokens_stream_4k.json")))
if os.environ.get("REHEARSAL"):
    traffic.update(traffic["rehearsal"])
    config.update(traffic["config"])
length, rows = traffic["row_tokens"], int(os.environ.get("ROWS", 8))
compile_log().arm()


def timed_steps(fn, tokens, label):
    t = time.perf_counter()
    out = fn({"tokens": tokens[:2]})
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: set-up ends here
    print(f"{label}: first call {time.perf_counter() - t:.2f} s", flush=True)
    times = []
    for lo in range(0, rows, 2):
        t = time.perf_counter()
        out = fn({"tokens": tokens[lo:lo + 2]})
        jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: one step's time
        times.append((time.perf_counter() - t) * 1e3)
    print(f"{label}: steps of 2 rows {[round(x, 2) for x in times]} ms, median "
          f"{statistics.median(times):.2f}", flush=True)
    return out


t = time.perf_counter()
weights = lm_weights.make_weights(config, seed)
jax.block_until_ready(weights)  # sparkdl-lint: allow[H1] -- a measure tool: the time of the draws is what it reads
print(f"weights {time.perf_counter() - t:.2f} s", flush=True)
mf = model_function(config, weights, length)
weights = None
tokens = lm_weights.token_rows(seed, rows, length, config["vocab_size"], 1.0)
out = timed_steps(mf, tokens, "loop on the device")
print("exit_pdf of the last step", np.asarray(out["exit_pdf"]).round(4).tolist(), flush=True)
stats = jax.devices()[0].memory_stats() or {}
print("memory", {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved", "bytes_limit")})

# -- (2) two steps under the profiler ---------------------------------------------------
scopes = max((e.scopes for e in compile_log().events() if e.scopes), key=len)
log_dir = os.path.join(ROOT, ".bench_trace")
steps = 2
jax.profiler.start_trace(log_dir)
for lo in range(0, 2 * steps, 2):
    out = mf({"tokens": tokens[lo % rows:lo % rows + 2]})
jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the traced steps end here
jax.profiler.stop_trace()
try:
    data = jax.profiler.ProfileData.from_file(tracing.find_trace_file(log_dir))
    planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
except FileNotFoundError:
    planes = []
seconds, count, spans, modules = {}, {}, {}, []
for plane in planes:
    print("plane", plane.name, "lines", [(line.name, len(list(line.events))) for line in plane.lines])
    for line in plane.lines:
        if line.name == "XLA Modules":
            modules += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
        if line.name == "XLA Ops":
            for e in line.events:
                m = re.match(r"^%?([\w.\-]+) = ", e.name)
                key = m.group(1) if m else e.name
                seconds[key] = seconds.get(key, 0.0) + e.duration_ns * 1e-9 / steps
                count[key] = count.get(key, 0) + 1
                if key.startswith("while"):
                    spans.setdefault(key, []).append((e.start_ns, e.duration_ns))
if planes:
    print("XLA Modules events", [(n, round(d * 1e-6, 2)) for n, _, d in modules])
    print(f"sum of all XLA Ops events a step {sum(seconds.values()) * 1e3:.1f} ms; without the `while` "
          f"events {sum(s for k, s in seconds.items() if not k.startswith('while')) * 1e3:.1f} ms")
    for key, evs in spans.items():
        print(f"  {key}: {len(evs)} events, {sum(d for _, d in evs) * 1e-6 / steps:.1f} ms a step, scope "
              f"{scopes.get(key)!r}; first at {evs[0][0]} for {evs[0][1]} ns")
    by_scope: dict = {}
    for name, s in seconds.items():
        if name.startswith("while"):
            continue
        path = [p for p in scopes.get(name, "(no scope)").split("/")
                if p not in ("while", "body", "cond", "closed_call")]
        by_scope["/".join(path[:3])] = by_scope.get("/".join(path[:3]), 0.0) + s
    print("device time a step by scope (the `while` events left out):")
    for kind, s in sorted(by_scope.items(), key=lambda kv: -kv[1])[:25]:
        print(f"  {s * 1e3:9.2f} ms  {kind}")
    print("the longest instructions, a step (ms, runs in the trace, ms a run):")
    for name, s in sorted(seconds.items(), key=lambda kv: -kv[1])[:28]:
        print(f"  {s * 1e3:9.2f} {count[name]:5d} {s * 1e3 * steps / count[name]:8.3f}  {name:34s} "
              f"{scopes.get(name, '')[-70:]}")
if os.environ.get("LOOP_ONLY"):
    sys.exit(0)


# -- (3) the same layers written out inside one pass ------------------------------------
def written_out(params, tokens_):
    eps = config["rms_norm_eps"]
    gate = params["exit_gate"]

    def one_pass(x, _):
        for p in params["layers"]:  # 48 bodies, 48 pallas_calls
            x = ouro.layer(p, x, config)
        h = lm_blocks.rms_norm(x, params["final_norm"], eps)
        return h, (h * gate["weight"]).sum(axis=-1) + gate["bias"]

    x = params["embed"][tokens_].astype(lm_blocks.F32)
    h, g = jax.lax.scan(one_pass, x, None, length=int(config["total_ut_steps"]))
    return {"logprobs": lm_blocks.score_head(h, tokens_, params["head"]), "exit_pdf": ouro.exit_pdf(g)}


params = dict(mf.params)
stacked = params.pop("layers")
depth = config["num_hidden_layers"]
mf = out = None
cut: list = [{} for _ in range(depth)]
for name in list(stacked):  # a leaf at a time, so that two copies of one leaf is the most there is
    leaf = stacked.pop(name)
    for i in range(depth):
        cut[i][name] = leaf[i]
    jax.block_until_ready(cut[-1][name])  # sparkdl-lint: allow[H1] -- a measure tool: the stacked leaf can go
    leaf = None
params["layers"] = cut
step = jax.jit(written_out)
out2 = timed_steps(lambda inputs: step(params, inputs["tokens"]), tokens, "48 layers written out")
print("exit_pdf of the last step", np.asarray(out2["exit_pdf"]).round(4).tolist(), flush=True)
stats = jax.devices()[0].memory_stats() or {}
print("memory", {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved", "bytes_limit")})
