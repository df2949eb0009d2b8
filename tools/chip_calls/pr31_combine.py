"""PR 31's look at the expert block's combine alone, outside the benchmark: the kernel
`moe_combine` at cell 4's shapes (16,384 tokens, 10 choices of 512 experts, 128 held,
rows of 2,048 in bfloat16) on seeded routing, against the parent's gather, float32 copy
and masked sum (`ops/moe.py` lines 176-180 at e9da041, kept here as `parent_combine`):
milliseconds a call and the largest difference, with a quarter of the assignments held
(the cell's case), all of them and none. Then the whole of `held_experts_ffn`, the
parent's (`.bench_parent`: git archive of e9da041, where present) beside the tree's.

    chiprun -- python3 tools/chip_calls/pr31_combine.py [name=value[,name=value] ...]

`name=value` sets a module constant of `ops/moe.py` for a run (`_TOKEN_BLOCK=256`): how
the constants that are there were chosen. Each argument is one setting, run in turn.
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

from sparkdl_tpu.ops import moe  # noqa: E402

N, K, WIDTH, HELD, D, F = (int(x) for x in os.environ.get(
    "SHAPE", "16384,10,512,128,2048,512").split(","))
TILE = 128


def routing(seed, pool):
    """``K`` distinct experts a token, drawn evenly from ``pool`` (a range of the
    router's width): the share held is the pool's overlap with ``[0, HELD)``."""
    rng = np.random.default_rng(seed)
    lo, hi = pool
    experts = lo + np.argsort(rng.random((N, hi - lo)), axis=1)[:, :K]
    weights = rng.random((N, K)) + 0.1
    weights /= weights.sum(axis=1, keepdims=True)
    return jnp.asarray(experts, jnp.int32), jnp.asarray(weights, jnp.float32)


def parent_combine(y_rows, dest, is_held, weights):
    """The parent's combine: ``y_rows`` is ``[R, D]``."""
    picked = y_rows[jnp.where(is_held, dest, 0).T].astype(jnp.float32)
    return jnp.sum(jnp.where(is_held.T[..., None], picked * weights.T[..., None], 0.0),
                   axis=0)


def moe_slabs(flat):
    """``[R, D]`` bfloat16 rows as `grouped_swiglu` keeps them."""
    words, lanes = moe.slab_shape(D, flat.dtype)
    bits = jax.lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
    bits = bits.reshape(-1, words, 2, lanes)
    return bits[:, :, 0] | (bits[:, :, 1] << 16)


def timed(fn, args, calls=10):
    out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the compile ends here
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the timed calls end here
    return out, (time.perf_counter() - t) / calls * 1e3


def apply(setting):
    for pair in filter(None, setting.split(",")):
        name, value = pair.split("=")
        setattr(moe, name, int(value))
    jax.clear_caches()  # `combine_held` is jitted: what it traced read the old constants


def load_parent():
    path = os.path.join(ROOT, ".bench_parent/sparkdl_tpu/ops/moe.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("parent_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    print(jax.devices()[0].device_kind, (N, K, WIDTH, HELD, D, F), flush=True)
    settings = [a for a in sys.argv[1:] if "=" in a] or [""]
    rows = moe.layout_rows(N * K, HELD, TILE)
    rng = np.random.default_rng(31)
    flat = jnp.asarray(rng.standard_normal((rows, D), np.float32), jnp.bfloat16)
    slabs = moe_slabs(flat)
    cases = {"25% held": (0, WIDTH), "100% held": (0, HELD), "0% held": (HELD, WIDTH)}
    layouts = {}
    for case, pool in cases.items():
        experts, weights = routing(31, pool)
        _, dest, is_held, _, _, _ = jax.jit(
            lambda e: moe.grouped_layout(e, 0, HELD, TILE))(experts)
        layouts[case] = (dest, is_held, weights)
        expected, ms = timed(jax.jit(parent_combine), (flat, dest, is_held, weights))
        layouts[case] += (expected,)
        print(f"{case:10s} parent's gather and sum {ms:8.3f} ms a call   "
              f"(held {float(jnp.mean(is_held)):.4f})", flush=True)
    for setting in settings:
        apply(setting)
        for case, (dest, is_held, weights, expected) in layouts.items():
            out, ms = timed(jax.jit(lambda *a: moe.combine_held(*a)),
                            (slabs, dest, is_held, weights))
            copies = int(jnp.sum(is_held))
            print(f"{case:10s} moe_combine {setting:24s} {ms:8.3f} ms a call   "
                  f"{copies} copies   largest difference "
                  f"{float(jnp.max(jnp.abs(out - expected))):.3e}", flush=True)
    # the whole block, at the cell's case
    experts, weights = routing(32, cases["25% held"])
    x = jnp.asarray(rng.standard_normal((N, D), np.float32))
    w = [jnp.asarray(rng.standard_normal(s, np.float32) / np.sqrt(s[1]), jnp.bfloat16)
         for s in ((HELD, D, F), (HELD, D, F), (HELD, F, D))]
    results = {}
    for name, module in (("parent", load_parent()), ("tree", moe)):
        if module is None:
            continue
        fn = jax.jit(lambda x, e, p, *w, m=module: m.held_experts_ffn(x, e, p, *w, first=0)[0])
        results[name], ms = timed(fn, (x, experts, weights, *w))
        print(f"held_experts_ffn, {name:6s} {ms:8.3f} ms a call", flush=True)
    if len(results) == 2:
        print("largest difference, tree against parent "
              f"{float(jnp.max(jnp.abs(results['tree'] - results['parent']))):.3e} "
              f"of {float(jnp.max(jnp.abs(results['parent']))):.3e}", flush=True)


if __name__ == "__main__":
    main()
