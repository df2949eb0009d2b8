"""PR 35: which rounding moved. On the chip the tree's cells 5 and 6 answer a few last bits
apart from the parent's (call 1), cell 4, whose output stays float32, to the bit. Two things
changed for `o_proj`'s operand: the kernel rounds `acc / l` to bfloat16 itself (Mosaic's
conversion) where XLA's `convert` did, and the product reads it in another layout. At cell
6's and cell 5's shapes, on seeded operands:

 (1) the kernel alone: `causal_attention(out_dtype=bfloat16)` against
     `causal_attention(out_dtype=float32).astype(bfloat16)`, bit for bit, and if not, how
     many values differ, by how many units in the last place, and which way a tie went;
 (2) the kernel and `o_proj`'s product in one program, the parent's form (its function from
     `.bench_parent`, heads-first float32, XLA's cast) against the tree's with the float32
     output (XLA's cast, the new layout) and with the bfloat16 output.

    chiprun -- python3 tools/chip_calls/pr35_rounding.py
    SMALL=1 JAX_PLATFORMS=cpu rehearses it at small shapes.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from sparkdl_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from sparkdl_tpu.models.lm_blocks import dot  # noqa: E402
from sparkdl_tpu.ops import attention as tree  # noqa: E402

spec = importlib.util.spec_from_file_location(
    "parent_attention", os.path.join(ROOT, ".bench_parent/sparkdl_tpu/ops/attention.py"))
parent = importlib.util.module_from_spec(spec)
spec.loader.exec_module(parent)

small = bool(os.environ.get("SMALL"))
SHAPES = {"cell 6 (16 on 16 heads of 128)": (2, 64 if small else 4096, 16, 16, 128, 2048),
          "cell 5 (64 heads of 128, no rotary pair here)": (2, 64 if small else 8192, 64, 64, 128, 7168)}
BF16 = jnp.bfloat16


def bits(x):
    return np.asarray(x).view(np.uint16).astype(np.int32)


for name, (b, t, hq, hkv, d, width) in SHAPES.items():
    if small:
        hq, hkv, width = 4, 4, 256
    keys = jax.random.split(jax.random.PRNGKey(35), 4)
    q, k, v = (jax.random.normal(key, (b, t, h, d), jnp.float32) for key, h in zip(keys, (hq, hkv, hkv)))
    w = (jax.random.normal(keys[3], (hq * d, width), jnp.float32) / (hq * d) ** 0.5).astype(BF16)
    scale = d ** -0.5
    print(f"== {name}: q {q.shape}, o_proj {w.shape}")
    # (1) the kernel alone
    whole = jax.jit(lambda q, k, v: tree.causal_attention(q, k, v, scale))(q, k, v)
    by_xla = whole.astype(BF16)
    by_kernel = jax.jit(lambda q, k, v: tree.causal_attention(q, k, v, scale, out_dtype=BF16))(q, k, v)
    apart = bits(by_xla) != bits(by_kernel)
    print(f"   (1) the kernel's own rounding against XLA's cast of its float32 output: {int(apart.sum())} of "
          f"{apart.size} values differ")
    if apart.any():
        f32 = np.asarray(whole)[apart]
        steps = bits(by_kernel)[apart] - bits(by_xla)[apart]
        # where XLA's cast (round to nearest, ties to even) and the kernel disagree: is the float32 value a tie?
        low = f32.view(np.uint32) & 0xFFFF
        print(f"       units in the last place apart: {np.unique(steps, return_counts=True)}; of the float32 values "
              f"{int((low == 0x8000).sum())} are exact ties, low halves range {hex(int(low.min()))}..{hex(int(low.max()))}")
    # (2) the kernel and o_proj's product in one program
    forms = {
        "parent (heads-first float32, XLA's cast)": lambda q, k, v, w: dot(
            parent.causal_attention(q, k, v, scale).reshape(b, t, -1), w),
        "tree, float32 output (XLA's cast)": lambda q, k, v, w: dot(
            tree.causal_attention(q, k, v, scale).reshape(b, t, -1), w),
        "tree, bfloat16 output": lambda q, k, v, w: dot(
            tree.causal_attention(q, k, v, scale, out_dtype=BF16).reshape(b, t, -1), w),
    }
    outs = {form: np.asarray(jax.jit(fn)(q, k, v, w)) for form, fn in forms.items()}
    first = outs["parent (heads-first float32, XLA's cast)"]
    for form, out in outs.items():
        gap = np.abs(out - first)
        print(f"   (2) {form}: {int((out != first).sum())} of {out.size} values differ from the parent's, "
              f"largest gap {gap.max():.3g} (values spread by {first.std():.3g})")
