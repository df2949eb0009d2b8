"""PR 33's look at the way into the expert block alone, outside the benchmark: the tree's
`grouped_swiglu`, which takes a tile's rows from `x` by one DMA a row through `row_token`,
against the parent's (`.bench_parent`: git archive of d1cff70), whose rows XLA gathers into
the grouped buffer `x_rows` first. At cell 5's shapes (16,384 tokens, 8 choices of 192
experts, 12 held, experts of 7,168 x 2,048) and cell 4's (10 of 512, 128 held, 2,048 x
512), bfloat16, seeded routing drawn evenly, with the even share of the assignments held
(6.25% and 25%), all of them and none. For each: tiles in use over tiles of the buffer; the
parent's gather, its kernel, and its `held_experts_ffn` whole; the tree's kernel and its
`held_experts_ffn` whole; the largest difference between the two answers (0 is expected:
the same rows reach the same products; any other ends the script with exit code 1).

    chiprun -- python3 tools/chip_calls/pr33_gather.py [name=value[,name=value] ...]

`name=value` sets a module constant of the tree's `ops/moe.py` for a run
(`_ISSUE_UNROLL=16`), each argument one setting, run in turn after the tree as it is.
`CELLS=5` or `CELLS=4` in the environment keeps to one cell's shapes; a cell that is six
numbers (`CELLS=256,3,8,3,256,512`: tokens, choices, the router's width, experts held, D,
F) rehearses the script on the CPU.
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

SHAPES = {  # cell: tokens, choices, the router's width, experts held, D, F
    "5": (16384, 8, 192, 12, 7168, 2048),
    "4": (16384, 10, 512, 128, 2048, 512),
}


def routing(seed, n, k, pool):
    """``k`` distinct experts a token, drawn evenly from ``pool`` (a range of the
    router's width): the share held is the pool's overlap with the experts held."""
    rng = np.random.default_rng(seed)
    lo, hi = pool
    experts = lo + np.argsort(rng.random((n, hi - lo)), axis=1)[:, :k]
    weights = rng.random((n, k)) + 0.1
    weights /= weights.sum(axis=1, keepdims=True)
    return jnp.asarray(experts, jnp.int32), jnp.asarray(weights, jnp.float32)


def timed(fn, args, calls=10):
    out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the compile ends here
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the timed calls end here
    return out, (time.perf_counter() - t) / calls * 1e3


def xla_slabs(x):
    """`ops/moe.py::row_slabs` left to XLA (the first form of PR 33): the cast, then two
    bfloat16 values to a word."""
    n, d = x.shape
    words, lanes = moe.slab_shape(d, jnp.bfloat16)
    halves = jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)
    halves = halves.astype(jnp.uint32).reshape(n, words, 2, lanes)
    return halves[:, :, 0] | (halves[:, :, 1] << 16)


def load_parent():
    path = os.path.join(ROOT, ".bench_parent/sparkdl_tpu/ops/moe.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("parent_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_AS_IT_IS = {}


def apply(setting):
    """The tree's constants as they are, then ``setting``'s over them."""
    for name, value in _AS_IT_IS.items():
        setattr(moe, name, value)
    for pair in filter(None, setting.split(",")):
        name, value = pair.split("=")
        _AS_IT_IS.setdefault(name, getattr(moe, name))
        setattr(moe, name, int(value))
    jax.clear_caches()  # the kernels are jitted: what they traced read the old constants


differences = []


def one_cell(cell, parent, settings):
    n, k, width, held, d, f = SHAPES.get(cell) or (int(v) for v in cell.split(","))
    tile = moe.row_tile(d, f, 2)
    print(f"cell {cell}: {(n, k, width, held, d, f)}, tiles of {tile} rows", flush=True)
    rng = np.random.default_rng(33)
    x32 = jnp.asarray(rng.standard_normal((n, d), np.float32))
    x = x32.astype(jnp.bfloat16)
    w = [jnp.asarray(rng.standard_normal(s, np.float32) / np.sqrt(s[1]), jnp.bfloat16)
         for s in ((held, d, f), (held, d, f), (held, f, d))]
    same = None
    for name, fn in (("the parent's cast to bfloat16", lambda a: a.astype(jnp.bfloat16)),
                     ("slabs by XLA", xla_slabs),
                     ("slabs by the kernel moe_slabs", lambda a: moe.row_slabs(a, jnp.bfloat16))):
        out, ms = timed(jax.jit(fn), (x32,))
        if out.dtype == jnp.uint32:
            same = out if same is None else bool(jnp.array_equal(out, same))
        print(f"  {name:32s} {ms:8.3f} ms", flush=True)
    print(f"  the two layings-out agree: {same}", flush=True)
    cases = {"even share held": (0, width), "everything held": (0, held),
             "nothing held": (held, width)}
    for case, pool in cases.items():
        experts, weights = routing(33, n, k, pool)
        row_token, _, is_held, tile_expert, tiles_used, _ = jax.jit(
            lambda e: moe.grouped_layout(e, 0, held, tile))(experts)
        print(f"  {case}: {int(tiles_used)} tiles in use of {tile_expert.shape[0]} "
              f"(held {100 * float(jnp.mean(is_held)):.2f}% of {n * k} assignments)", flush=True)
        whole = lambda m: jax.jit(lambda a, e, p, *ms: m.held_experts_ffn(
            a, e, p, *ms, first=0)[0])
        expected = None
        if parent is not None:
            gather = jax.jit(lambda a, rt: jnp.concatenate(
                [a, jnp.zeros((1, d), a.dtype)])[rt])
            x_rows, gather_ms = timed(gather, (x, row_token))
            # a slice out: ten whole results in flight would be 20 GB
            _, kernel_ms = timed(jax.jit(lambda r, te, tu, *ms: parent.grouped_swiglu(
                r, te, tu, *ms, tile)[:8]), (x_rows, tile_expert, tiles_used, *w))
            del x_rows
            expected, block_ms = timed(whole(parent), (x32, experts, weights, *w))
            print(f"    parent: gather {gather_ms:8.3f} ms   moe_experts {kernel_ms:8.3f} ms   "
                  f"held_experts_ffn {block_ms:8.3f} ms", flush=True)
        for setting in settings:
            apply(setting)
            _, kernel_ms = timed(jax.jit(lambda a, rt, te, tu, *ms: moe.grouped_swiglu(
                a, rt, te, tu, *ms, tile=tile)[:8]), (x, row_token, tile_expert, tiles_used, *w))
            y, block_ms = timed(whole(moe), (x32, experts, weights, *w))
            apart = ""
            if expected is not None:
                gap = float(jnp.max(jnp.abs(y - expected)))
                apart = (f"   largest difference from the parent {gap:.3e}"
                         f" of {float(jnp.max(jnp.abs(expected))):.3e}")
                differences.append(gap)
            print(f"    tree {setting:22s}: moe_experts {kernel_ms:8.3f} ms   "
                  f"held_experts_ffn {block_ms:8.3f} ms{apart}", flush=True)
        apply("")


def main():
    print(jax.devices()[0].device_kind, flush=True)
    settings = [""] + [a for a in sys.argv[1:] if "=" in a]
    parent = load_parent()
    if parent is None:
        print("no .bench_parent: the tree alone", flush=True)
    for cell in os.environ.get("CELLS", "5 4").split():
        one_cell(cell, parent, settings)
    return 1 if any(differences) else 0


if __name__ == "__main__":
    sys.exit(main())
