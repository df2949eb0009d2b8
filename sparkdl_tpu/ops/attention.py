"""Causal softmax attention over long rows without the score tensor.

At 8,192 positions, 16 heads and 2 rows the scores of one layer are
4.3 GB in float32. The Pallas kernel ``attention`` never writes them:
the grid walks (row, head, block of queries, block of keys), a
``block_q x block_k`` tile of scores lives in VMEM only, and a running
maximum, a running sum and an unnormalised accumulator (float32
scratch) carry the softmax across the key blocks of one query block
(the online softmax of Milakov and Gimelshein 2018, as FlashAttention
tiles it). Key blocks wholly above the diagonal are skipped without a
fetch (their index map names the last block needed instead); only the
blocks the diagonal crosses pay for the mask. Grouped queries: query
head ``h`` reads key head ``h // (Hq / Hkv)`` through the index map,
and the key heads are never repeated in memory.

Products take operands of the parameters' type (bfloat16) and
accumulate in float32; the softmax's statistics are float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernel's name: what its instruction is called in the device trace
SCOPE = "attention"

_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _use_interpreter() -> bool:
    """Off the TPU the kernel runs in Pallas's interpreter."""
    return jax.default_backend() != "tpu"


def _attention_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      block_q: int, block_k: int, lanes: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    d = q_ref.shape[-1]
    precision = (lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    first_row, last_row = qi * block_q, qi * block_q + block_q - 1
    first_col, last_col = ki * block_k, ki * block_k + block_k - 1

    def step(masked: bool):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=precision)  # [block_q, block_k]
        if masked:
            rows = first_row + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = first_col + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, _MASKED)
        # the statistics are `lanes` wide, every lane alike
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - jnp.tile(m_next, (1, block_k // lanes)))
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_ref[...] = m_next
        acc_ref[...] = (acc_ref[...] * jnp.tile(alpha, (1, d // lanes))
                        + lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32,
                                  precision=precision))

    # a key block wholly below the diagonal needs no mask; one the
    # diagonal crosses does; one wholly above it is skipped
    pl.when(last_col <= first_row)(functools.partial(step, False))
    pl.when((last_col > first_row) & (first_col <= last_row))(
        functools.partial(step, True))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / jnp.tile(l_ref[...], (1, d // lanes))
                       ).astype(o_ref.dtype)


def causal_attention(q, k, v, scale: float, block: int = 512,
                     dtype=jnp.bfloat16):
    """``softmax(q k^T * scale + causal mask) v`` per head.

    ``q``: ``[B, T, Hq, d]``; ``k``, ``v``: ``[B, T, Hkv, d]`` with
    ``Hq`` a multiple of ``Hkv`` (query head ``h`` reads key head
    ``h // (Hq / Hkv)``). ``dtype`` is what the two products round their
    operands to; ``block`` is the tile's side in positions. Returns
    float32 ``[B, T, Hq, d]``."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    block = min(block, -(-t // 8) * 8)
    padded = -(-t // block) * block
    lanes = math.gcd(128, block, d)

    def heads_first(x):  # [B, T, H, d] -> [B, H, T', d], zeros past T
        x = jnp.swapaxes(x.astype(dtype), 1, 2)
        return jnp.pad(x, ((0, 0), (0, 0), (0, padded - t), (0, 0)))

    q, k, v = heads_first(q * scale), heads_first(k), heads_first(v)
    n = padded // block

    def query_block(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def key_block(bi, hi, qi, ki):
        # past the diagonal: the last block needed, already there
        return bi, hi // group, jnp.minimum(ki, qi), 0

    out = pl.pallas_call(
        functools.partial(_attention_kernel, block_q=block, block_k=block,
                          lanes=lanes),
        out_shape=jax.ShapeDtypeStruct((b, hq, padded, d), jnp.float32),
        grid=(b, hq, n, n),
        in_specs=[pl.BlockSpec((1, 1, block, d), query_block),
                  pl.BlockSpec((1, 1, block, d), key_block),
                  pl.BlockSpec((1, 1, block, d), key_block)],
        out_specs=pl.BlockSpec((1, 1, block, d), query_block),
        scratch_shapes=[pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_use_interpreter(),
        name=SCOPE,
    )(q, k, v)
    return jnp.swapaxes(out[:, :, :t], 1, 2)
