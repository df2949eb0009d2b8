"""PR 33: what one DMA a row costs, alone. A kernel that does nothing but copy rows of an
array in HBM (`[16384, words, 128]` 32-bit words, a row a slab as `ops/moe.py::row_slabs`
lays it out) into VMEM, 256 a grid step through row numbers in scalar memory, and waits
for them: nanoseconds a row and GB/s, for slabs of cell 5 (28 words: 14 KB), cell 4 (8
words: 4 KB) and their float32 twins, at the default priority and at priority 1. What
`grouped_swiglu`'s copies have to hide under (`PERF.md` section 6, PR 33).

    chiprun -- python3 tools/chip_calls/pr33_row_dma.py

A row of an array in its own `[N, D]` layout cannot be copied alone: Mosaic refuses a slice
of one row of a tiled dimension ("Slice shape along dimension 0 must be aligned to tiling
(8), but is 1"; the TPU compiler in the sandbox, PR 33), which is why the rows are slabs.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N, ROWS, STEPS, UNROLL = 16384, 256, 64, 8
INTERPRET = jax.default_backend() != "tpu"


def copier(words, dtype, priority):
    def kernel(row_ref, x_ref, o_ref, buf, sem):
        copy = lambda row, r: pltpu.make_async_copy(
            x_ref.at[row], buf.at[pl.ds(r * words, words)], sem)

        def turn(i, carry):
            for u in range(UNROLL):
                r = i * UNROLL + u
                copy(row_ref[r], r).start(priority=priority)
            return carry
        lax.fori_loop(0, ROWS // UNROLL, turn, 0)
        lax.fori_loop(0, ROWS, lambda i, c: (copy(0, 0).wait(), c)[1], 0)
        o_ref[...] = lax.bitcast_convert_type(buf[pl.ds(0, 8), :], jnp.float32)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((STEPS * 8, 128), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(STEPS,),
            in_specs=[pl.BlockSpec((1024,), lambda i: (i,), memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            scratch_shapes=[pltpu.VMEM((ROWS * words, 128), dtype),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True,
            vmem_limit_bytes=100 << 20),
        interpret=INTERPRET, name="row_dma")


def main():
    print(jax.devices()[0].device_kind, flush=True)
    rows = np.zeros((STEPS, 1024), np.int32)
    rows[:, :ROWS] = np.random.default_rng(33).integers(0, N, (STEPS, ROWS))
    rows = jnp.asarray(rows.reshape(-1))
    for name, words, dtype in (("cell 5, bfloat16 pairs", 28, jnp.uint32),
                               ("cell 5, float32", 56, jnp.float32),
                               ("cell 4, bfloat16 pairs", 8, jnp.uint32),
                               ("cell 4, float32", 16, jnp.float32)):
        x = jnp.zeros((N, words, 128), dtype)
        for priority in (0, 1):
            fn = jax.jit(copier(words, dtype, priority))
            out = fn(rows, x)
            jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the compile ends here
            t = time.perf_counter()
            for _ in range(10):
                out = fn(rows, x)
            jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the timed calls end here
            s = (time.perf_counter() - t) / 10
            print(f"{name:24s} slab [{words:2d}, 128] priority {priority}: {s * 1e3:7.3f} ms for "
                  f"{STEPS * ROWS} rows, {s / (STEPS * ROWS) * 1e9:6.1f} ns a row, "
                  f"{STEPS * ROWS * words * 512 / s / 1e9:6.1f} GB/s", flush=True)


if __name__ == "__main__":
    main()
