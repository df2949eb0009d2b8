"""PR 35's look round the attention kernel, outside the benchmark: one cell's model function
over seeded weights and a few rows of its traffic, built once for each form of
`ops/attention.py::causal_attention`'s call, all in one process over one set of weights:

  parent   the parent's function (`.bench_parent/sparkdl_tpu/ops/attention.py`, loaded beside the
           tree's) under the parent's call: every operand copied heads-first, a float32
           heads-first output that the caller casts, transposes and copies;
  cut      (cell 5 only) the tree's function with q, k and v read in place and the output
           written for `o_proj`, but `k_nope` and `v` cut out of `kv` by XLA first (ISSUE 35's
           items 1 and 2 without the second half of item 3);
  change   the tree as it is.

For each: the first call, every step of 2 rows timed alone, whether every answer equals the
parent's to the last bit, then two steps under the profiler: device time by scope and the
instructions of one attention block (cell 5: layer 1; cell 4: layer 3; cell 6: the layer body),
each with how often it ran.

    chiprun -- python3 tools/chip_calls/pr35_profile.py axk1|ouro|qwen3next [seed]
    ROWS=4 REHEARSAL=1 JAX_PLATFORMS=cpu rehearses it at the traffic file's rehearsal sizes.
"""
import functools
import importlib.util
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

from benchmarks import lm_weights, program_lm, tracing  # noqa: E402
from benchmarks.drivers import token_stream_looped, token_stream_routed  # noqa: E402
from sparkdl_tpu.obs import compile_log  # noqa: E402
from sparkdl_tpu.ops import attention as attention_op  # noqa: E402

CELLS = {  # configuration, traffic, how the driver builds the function, the block whose instructions are listed
    "axk1": ("axk1_ep16", "tokens_stream_p16", functools.partial(token_stream_routed.model_function, routing_stats=True), r"LatentAttention_1\b"),
    "ouro": ("ouro_2p6b", "tokens_stream_4k", token_stream_looped.model_function, r"ut_loop/"),
    "qwen3next": ("qwen3next_80b_a3b_ep4", "tokens_stream", functools.partial(program_lm.model_function, routing_stats=True), r"GatedAttention_3\b"),
}
cell = sys.argv[1]
seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2147690001
config_name, traffic_name, model_function, block = CELLS[cell]
config = json.load(open(os.path.join(ROOT, f"benchmarks/configs/{config_name}.json")))
traffic = json.load(open(os.path.join(ROOT, f"benchmarks/traffic/{traffic_name}.json")))
if os.environ.get("REHEARSAL"):
    traffic.update(traffic["rehearsal"])
    config.update(traffic["config"])
length, rows = traffic["row_tokens"], int(os.environ.get("ROWS", 8))
compile_log().arm()

tree_attention = attention_op.causal_attention
spec = importlib.util.spec_from_file_location(
    "parent_attention", os.path.join(ROOT, ".bench_parent/sparkdl_tpu/ops/attention.py"))
parent_attention = importlib.util.module_from_spec(spec)
spec.loader.exec_module(parent_attention)


def _cut(x):
    return x.x[..., x.start:x.start + x.width] if isinstance(x, attention_op.HeadSlice) else x


def parent_form(q, k, v, scale, out_dtype=None, in_place=(), **kwargs):
    return parent_attention.causal_attention(q, _cut(k), _cut(v), scale, **kwargs)


def cut_form(q, k, v, scale, **kwargs):
    return tree_attention(q, _cut(k), _cut(v), scale, **kwargs)


FORMS = {"parent": parent_form, **({"cut": cut_form} if cell == "axk1" else {}), "change": tree_attention}

t = time.perf_counter()
weights = lm_weights.make_weights(config, seed)
jax.block_until_ready(weights)  # sparkdl-lint: allow[H1] -- a measure tool: set-up ends here
print(f"{cell} seed {seed}: weights {time.perf_counter() - t:.1f} s", flush=True)
tokens = lm_weights.token_rows(seed, rows, length, config["vocab_size"], 1.0)
log_dir = os.path.join(ROOT, ".bench_trace")
answers = {}
for form, fn in FORMS.items():
    attention_op.causal_attention = fn  # the models look the name up when traced: the first call
    mf = model_function(config, weights, length)
    known = len(compile_log().events())
    t = time.perf_counter()
    outs = [mf({"tokens": tokens[:2]})]
    jax.block_until_ready(outs)  # sparkdl-lint: allow[H1] -- a measure tool: the first call ends here
    print(f"== {form}: first call {time.perf_counter() - t:.1f} s", flush=True)
    scopes = max((e.scopes for e in compile_log().events()[known:] if e.scopes), key=len, default={})
    times = []
    for lo in range(0, rows, 2):
        t = time.perf_counter()
        out = mf({"tokens": tokens[lo:lo + 2]})
        jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: one step's time
        times.append((time.perf_counter() - t) * 1e3)
        outs.append(out)
    print(f"   steps of 2 rows {[round(x, 2) for x in times]} ms, median {statistics.median(times):.2f}", flush=True)
    answers[form] = [{k: np.asarray(v) for k, v in out.items()} for out in outs]
    same = all(np.array_equal(a[k], b[k]) for a, b in zip(answers[form], answers["parent"]) for k in a)
    worst = max(float(np.max(np.abs(a["logprobs"] - b["logprobs"]))) for a, b in zip(answers[form], answers["parent"]))
    print(f"   every answer the parent's to the last bit: {same} (largest gap of a log-probability {worst:.3g})")
    stats = jax.devices()[0].memory_stats() or {}
    print("   memory", {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved")})
    steps = 2
    jax.profiler.start_trace(log_dir)
    for lo in range(0, 2 * steps, 2):
        out = mf({"tokens": tokens[lo % rows:lo % rows + 2]})
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the traced steps end here
    jax.profiler.stop_trace()
    try:
        data = jax.profiler.ProfileData.from_file(tracing.find_trace_file(log_dir))
        planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    except FileNotFoundError:  # a rehearsal on the CPU
        planes = []
    seconds, count, modules = {}, {}, []
    for plane in planes:
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules += [round(e.duration_ns * 1e-6, 2) for e in line.events]
            if line.name == "XLA Ops":
                for e in line.events:
                    m = re.match(r"^%?([\w.\-]+) = ", e.name)
                    key = m.group(1) if m else e.name
                    seconds[key] = seconds.get(key, 0.0) + e.duration_ns * 1e-9 / steps
                    count[key] = count.get(key, 0) + 1
    for name in [n for n in seconds if n.startswith("while")]:  # a loop's own event spans its body's
        del seconds[name]
    print(f"   XLA Modules events {modules} ms; device time a step {sum(seconds.values()) * 1e3:.1f} ms")
    by_scope: dict = {}
    for name, s in seconds.items():
        path = [p for p in scopes.get(name, "(no scope)").split("/") if p not in ("while", "body", "cond", "closed_call")]
        kind = "/".join(re.sub(r"_\d+$", "", p) for p in path[:3])
        by_scope[kind] = by_scope.get(kind, 0.0) + s
    for kind, s in sorted(by_scope.items(), key=lambda kv: -kv[1])[:14]:
        print(f"   {s * 1e3:9.2f} ms  {kind}")
    print(f"   instructions under {block!r} and under no scope, 0.02 ms a run and more (ms a step, runs, ms a run):")
    for name, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
        path = scopes.get(name, "")
        a_run = s * 1e3 * steps / count[name]
        if a_run >= 0.02 and (not path or re.search(block, path)):
            print(f"   {s * 1e3:9.3f} {count[name]:5d} {a_run:8.3f}  {name:36s} {path[-84:]}")
    mf = outs = out = None
attention_op.causal_attention = tree_attention
