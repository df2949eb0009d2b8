"""PR 29's look at the kernel `gdn_scan` alone, outside the benchmark: the rule at the
cell's shapes (`[2, 32, 8192, 128]`, chunk 64, bfloat16 products) on seeded inputs, its
time a call against the parent's jnp chain (`.bench_parent`: git archive of 3d9c7a6) and
both against the reference's token-by-token recurrence in float32 on a few heads.

    chiprun -- python3 tools/chip_calls/pr29_kernel.py [name=value ...]

`name=value` sets a module constant of `ops/gated_delta.py` for the run (`_TILES=4`): how
the constants that are there were chosen. Last, the hard chunk of `tests/test_qwen3_next.py` (keys correlated
within a chunk, beta 0.95, slow decay, float32 products) against a float64 recurrence: the
error of the parent's `Precision.HIGH` chain on the chip is what that test's limit is set by.
"""
import importlib.util
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.reference import qwen3_next as reference  # noqa: E402
from sparkdl_tpu.ops import gated_delta  # noqa: E402

B, H, T, D = (int(x) for x in os.environ.get("SHAPE", "2,32,8192,128").split(","))


def inputs(seed):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, H, T, D)).astype(np.float32)
    k = rng.normal(size=(B, H, T, D)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(D)
    v = rng.normal(size=(B, H, T, D)).astype(np.float32)
    g = -rng.uniform(0.001, 0.7, size=(B, H, T)).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, size=(B, H, T)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


def timed(fn, args, calls=10):
    out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the compile ends here
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the timed calls end here
    return out, (time.perf_counter() - t) / calls * 1e3


def gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def hard_chunk(seed, t=128, h=2, d=128, spread=2.0):
    """As `tests/test_qwen3_next.py::_hard_chunk`: inputs and the float64 answer."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(1, h, 1, d)) + spread * rng.normal(size=(1, h, t, d))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(1, h, t, d))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    v = rng.normal(size=(1, h, t, d))
    g, beta = np.full((1, h, t), -0.001), np.full((1, h, t), 0.95)
    o = np.zeros_like(v)
    for hi in range(h):
        s = np.zeros((d, d))
        for ti in range(t):
            s = s * np.exp(g[0, hi, ti])
            s = s + np.outer(k[0, hi, ti], beta[0, hi, ti] * (v[0, hi, ti] - s.T @ k[0, hi, ti]))
            o[0, hi, ti] = s.T @ q[0, hi, ti]
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)), o


def main():
    args = inputs(29)
    print(jax.devices()[0].device_kind, (B, H, T, D), flush=True)
    # the oracle: two heads of one row, position by position, float32
    few = tuple(jnp.swapaxes(a[:1, :2], 1, 2) for a in args)
    oracle = np.asarray(jnp.swapaxes(reference.delta_rule_recurrence(*few), 1, 2))  # sparkdl-lint: allow[H1] -- a measure tool: the oracle is compared on the host
    parent_path = os.path.join(ROOT, ".bench_parent/sparkdl_tpu/ops/gated_delta.py")
    parent = None
    if os.path.exists(parent_path):
        spec = importlib.util.spec_from_file_location("parent_gated_delta", parent_path)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        out, ms = timed(jax.jit(parent.gated_delta_rule), args)
        print(f"parent chain   {ms:8.3f} ms a call   gap to the recurrence {gap(out[:1, :2], oracle):.3e}", flush=True)
    settings = [a for a in sys.argv[1:] if "=" in a] or [""]
    for setting in settings:
        for pair in filter(None, setting.split(",")):
            name, value = pair.split("=")
            setattr(gated_delta, name, int(value))
        out, ms = timed(jax.jit(lambda *a: gated_delta.gated_delta_rule(*a)), args)
        print(f"gdn_scan {setting:12s} {ms:8.3f} ms a call   gap to the recurrence {gap(out[:1, :2], oracle):.3e}", flush=True)
    for seed in (5, 6, 7):
        hard, exact = hard_chunk(seed)
        line = f"hard chunk, seed {seed}: gdn_scan {gap(gated_delta.gated_delta_rule(*hard, dtype=jnp.float32), exact):.3e}"
        if parent is not None:
            line += f"   parent chain {gap(parent.gated_delta_rule(*hard, dtype=jnp.float32), exact):.3e}"
        print(line, flush=True)


if __name__ == "__main__":
    main()
