"""A look at cell 4's delta-rule block, outside the benchmark: the cell's model function over
seeded weights and a few rows of its traffic, built once for each form of
`models/qwen3_next.py::gated_delta_net`, all in one process over one set of weights:

  parent       the parent's block (`.bench_parent`: git archive of dbf68fb; its `models/qwen3_next.py`
               over its own `ops/gated_delta.py`): one in-projection product of 96 heads, q, k and v
               cut out of its result, q and k normalised and repeated to the 32 value heads, three
               arrays into the kernel;
  one_product  the tree's block with the in-projection one product again, `[q | k | v]` and z sliced
               out of its result (a lead: what splitting the product is worth alone, and, where the
               answers differ, whether the split or the normalisation moved them);
  change       the tree as it is: `[q | k | v]` and z two products, q and k normalised in the 64-head
               array the convolution's fusion writes, the kernel reading all three out of it.

For each: the first call, every step of 2 rows timed alone, whether every answer equals the parent's
to the last bit, the device's peak memory, then two steps under the profiler: device time by scope
and the instructions of one delta-rule layer (`GatedDeltaNet_0`), each with how often it ran. Last,
layer 0's block alone on seeded activations, each form's output against the parent's, bit for bit.
Exit code 1 if an answer of the change differs from the parent's.

    python3 tools/chip_calls/pr43_profile.py [seed]   (on a TPU host)
    ROWS=4 REHEARSAL=1 JAX_PLATFORMS=cpu rehearses it at the traffic file's rehearsal sizes.
"""
import functools
import importlib.util
import json
import math
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pr37_layout import device_time  # noqa: E402  (it configures the compile cache first)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import lm_weights, program_lm  # noqa: E402
from sparkdl_tpu.models import qwen3_next  # noqa: E402
from sparkdl_tpu.obs import compile_log  # noqa: E402
from sparkdl_tpu.ops import gated_delta  # noqa: E402

BLOCK = r"GatedDeltaNet_0\b"


def load_parent():
    """The parent's `gated_delta_net`, over the parent's kernel."""
    modules = {}
    for name, path in (("gated_delta", "ops/gated_delta.py"), ("qwen3_next", "models/qwen3_next.py")):
        spec = importlib.util.spec_from_file_location(
            f"parent_{name}", os.path.join(ROOT, ".bench_parent/sparkdl_tpu", path))
        modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(modules[name])
    modules["qwen3_next"].gated_delta = modules["gated_delta"]
    return modules["qwen3_next"].gated_delta_net


def one_product(p, x, config):
    """The tree's block with the in-projection one product, as the parent computed it."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk = config["linear_key_head_dim"]
    w_in = p["in_proj_qkvz"]
    dtype = w_in.dtype
    qkvz = jnp.einsum("btd,dhk->bhtk", x.astype(dtype), w_in.reshape(w_in.shape[0], -1, dk),
                      preferred_element_type=jnp.float32)
    qkv, z = qkvz[:, :2 * hk + hv], qkvz[:, 2 * hk + hv:]
    ba = jnp.swapaxes(qwen3_next._dot(x, p["in_proj_ba"]), 1, 2)
    qkv = jax.nn.silu(qwen3_next.causal_depthwise_conv(qkv, p["conv"].reshape(-1, 2 * hk + hv, dk)))
    qkv = qwen3_next._l2_normalise(qkv, 2 * hk)
    head = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * hk + hv, 1, 1), 1)
    qkv = jnp.where(head < hk, qkv * (1.0 / math.sqrt(dk)), qkv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(jnp.float32)[:, None])
    o = gated_delta.gated_delta_rule(gated_delta.Heads(qkv, 0, hk), gated_delta.Heads(qkv, hk, hk),
                                     gated_delta.Heads(qkv, 2 * hk, hv), g, beta, dtype=dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + config["rms_norm_eps"])
    o = o * p["norm"].astype(jnp.float32) * jax.nn.silu(z)
    w_out = p["out_proj"]
    return jnp.einsum("bhtv,hvd->btd", o.astype(dtype), w_out.reshape(hv, dk, w_out.shape[-1]),
                      preferred_element_type=jnp.float32)


def main(seed):
    config = json.load(open(os.path.join(ROOT, "benchmarks/configs/qwen3next_80b_a3b_ep4.json")))
    traffic = json.load(open(os.path.join(ROOT, "benchmarks/traffic/tokens_stream.json")))
    if os.environ.get("REHEARSAL"):
        traffic.update(traffic["rehearsal"])
        config.update(traffic["config"])
    length, rows = traffic["row_tokens"], int(os.environ.get("ROWS", 8))
    compile_log().arm()
    t = time.perf_counter()
    weights = lm_weights.make_weights(config, seed)
    jax.block_until_ready(weights)  # sparkdl-lint: allow[H1] -- a measure tool: set-up ends here
    print(f"qwen3next seed {seed}: weights {time.perf_counter() - t:.1f} s", flush=True)
    tokens = lm_weights.token_rows(seed, rows, length, config["vocab_size"], 1.0)
    tree = qwen3_next.gated_delta_net
    forms = {"parent": load_parent(), "one_product": one_product, "change": tree}
    answers, differences, params = {}, [], None
    for form, fn in forms.items():
        qwen3_next.gated_delta_net = fn  # `final_hidden` looks the name up when traced: the first call
        mf = program_lm.model_function(config, weights, length)
        params = params or mf.params
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
        print(f"   steps of 2 rows {[round(x, 2) for x in times]} ms, median {statistics.median(times):.2f}",
              flush=True)
        answers[form] = [{k: np.asarray(v) for k, v in out.items()} for out in outs]
        same = all(np.array_equal(a[k], b[k]) for a, b in zip(answers[form], answers["parent"]) for k in a)
        worst = max(float(np.max(np.abs(a["logprobs"] - b["logprobs"])))
                    for a, b in zip(answers[form], answers["parent"]))
        differences.extend([] if same or form != "change" else ["the step"])
        print(f"   every answer the parent's to the last bit: {same} (largest gap of a log-probability {worst:.3g})")
        stats = jax.devices()[0].memory_stats() or {}
        print("   memory", {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved")})
        steps = 2
        seconds, count, modules = device_time(
            lambda i: mf({"tokens": tokens[2 * i % rows:2 * i % rows + 2]}), steps)
        print(f"   XLA Modules events {modules} ms; device time a step {sum(seconds.values()) * 1e3:.2f} ms")
        by_scope: dict = {}
        for name, s in seconds.items():
            path = scopes.get(name, "(no scope)").split("/")
            kind = "/".join(re.sub(r"_\d+$", "", p) for p in path[:2])
            by_scope[kind] = by_scope.get(kind, 0.0) + s
        for kind, s in sorted(by_scope.items(), key=lambda kv: -kv[1])[:16]:
            print(f"   {s * 1e3:9.2f} ms  {kind}")
        print(f"   instructions under {BLOCK!r}, 0.005 ms a run and more (ms a step, runs, ms a run):")
        for name, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
            path = scopes.get(name, "")
            a_run = s * 1e3 * steps / count[name]
            if a_run >= 0.005 and re.search(BLOCK, path):
                print(f"   {s * 1e3:9.3f} {count[name]:5d} {a_run:8.3f}  {name:40s} {path[-60:]}")
        mf = outs = out = None
    qwen3_next.gated_delta_net = tree

    # layer 0's block alone: where the step's answers differ, which form moved them
    program_config = dict(config, num_experts=config["router_width"])
    layer = params["layer_0"]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(seed % 2**31), (2, length, config["hidden_size"]), jnp.float32)
    outputs = {form: np.asarray(jax.jit(functools.partial(fn, config=program_config))(layer, x))
               for form, fn in forms.items()}
    for form, y in outputs.items():
        same = np.array_equal(y, outputs["parent"])
        differences.extend([] if same or form != "change" else ["the block alone"])
        print(f"layer 0's block alone, {form}: the parent's to the last bit {same}, "
              f"largest gap {float(np.max(np.abs(y - outputs['parent']))):.3g}")
    return 1 if differences else 0


if __name__ == "__main__":
    print(jax.devices()[0].device_kind, flush=True)
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 2147743001))
