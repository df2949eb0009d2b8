"""PR 32's look at the expert block alone, outside the benchmark, at cell 5's shapes
(16,384 tokens, 8 choices of 192 experts, 12 held, experts of 7,168 x 2,048 in bfloat16,
seeded routing drawn evenly, so 6.25% of the assignments held and some 683 an expert):
the kernel `moe_experts` (`grouped_swiglu`) and the whole of `held_experts_ffn` (layout,
the rows' way in (PR 32: XLA's gather into the grouped buffer; since PR 33 `moe_slabs` and
the kernel's own row copies), kernel, combine), milliseconds a call, for each pair of
rows a tile and columns of the expert's width a grid step. How `ops/moe.py::row_tile`'s
256 and `_WEIGHTS_VMEM`'s 512 columns were checked (`PERF.md` section 6, PR 32).

    chiprun -- python3 tools/chip_calls/pr32_experts.py [tile,block ...]

A pair the kernel's own VMEM budget refuses is run with the budget cut to 120 MiB (the
tool replaces `vmem_limit_bytes` where it is larger) and marked so; one that the
compiler refuses all the same prints the refusal's first line.
"""
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
    "SHAPE", "16384,8,192,12,7168,2048").split(","))
VMEM_CAP = 120 << 20
capped = []


def cap_vmem():
    """`pltpu.CompilerParams` with `vmem_limit_bytes` held to `VMEM_CAP`."""
    params = moe.pltpu.CompilerParams

    def held_to_cap(**kwargs):
        if kwargs.get("vmem_limit_bytes", 0) > VMEM_CAP:
            capped.append(kwargs["vmem_limit_bytes"])
            kwargs["vmem_limit_bytes"] = VMEM_CAP
        return params(**kwargs)
    moe.pltpu.CompilerParams = held_to_cap


def timed(fn, args, calls=20):
    out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the compile ends here
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the timed calls end here
    return out, (time.perf_counter() - t) / calls * 1e3


def main():
    print(jax.devices()[0].device_kind, (N, K, WIDTH, HELD, D, F), flush=True)
    pairs = [tuple(int(v) for v in a.split(",")) for a in sys.argv[1:]] or [
        (tile, block) for tile in (128, 256, 512) for block in (256, 512)]
    cap_vmem()
    rng = np.random.default_rng(32)
    experts = jnp.asarray(np.argsort(rng.random((N, WIDTH)), axis=1)[:, :K], jnp.int32)
    weights = rng.random((N, K)) + 0.1
    weights = jnp.asarray(2.5 * weights / weights.sum(axis=1, keepdims=True), jnp.float32)
    x = jnp.asarray(rng.standard_normal((N, D), np.float32), jnp.bfloat16)
    w = [jnp.asarray(rng.standard_normal(s, np.float32) / np.sqrt(s[1]), jnp.bfloat16)
         for s in ((HELD, D, F), (HELD, D, F), (HELD, F, D))]
    first = None
    for tile, block in pairs:
        moe._WEIGHTS_VMEM = 2 * 3 * D * block * 2
        jax.clear_caches()  # `combine_held` is jitted: what it traced read the old constant
        del capped[:]
        assert moe.width_block(D, F, 2) == min(block, F), (block, moe.width_block(D, F, 2))
        row_token, _, is_held, tile_expert, tiles_used, _ = jax.jit(
            lambda e: moe.grouped_layout(e, 0, HELD, tile))(experts)
        label = (f"tile {tile:4d} block {block:5d}  rows {row_token.shape[0]:7d}  tiles in use "
                 f"{int(tiles_used):4d} (held {100 * float(jnp.mean(is_held)):.2f}%)")
        try:
            # a slice out: ten whole results in flight would be 19 GB (since PR 33 the kernel
            # takes `x` and `row_token`, and the time holds `moe_slabs` beside `moe_experts`)
            _, kernel_ms = timed(jax.jit(lambda a, rt, te, tu, *m: moe.grouped_swiglu(
                a, rt, te, tu, *m, tile=tile)[:8]), (x, row_token, tile_expert, tiles_used, *w))
            y, block_ms = timed(jax.jit(lambda a, e, p, *m: moe.held_experts_ffn(
                a, e, p, *m, first=0, tile=tile)[0]), (x, experts, weights, *w))
        except Exception as e:  # noqa: BLE001 -- the compiler's refusal is the reading
            print(f"{label}  refused: {str(e).splitlines()[0][:200]}", flush=True)
            continue
        first = y if first is None else first
        print(f"{label}  moe_experts {kernel_ms:7.3f} ms ({kernel_ms / int(tiles_used):.4f} a tile)  "
              f"held_experts_ffn {block_ms:7.3f} ms  largest difference from the first pair "
              f"{float(jnp.max(jnp.abs(y - first))):.2e} of {float(jnp.max(jnp.abs(first))):.2e}"
              + (f"  (VMEM budget {capped[0] >> 20} MiB cut to {VMEM_CAP >> 20})" if capped else ""),
              flush=True)


if __name__ == "__main__":
    main()
