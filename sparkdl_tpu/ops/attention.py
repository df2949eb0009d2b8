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
and the key heads are never repeated in memory. Values may be of
another size than keys, and the scores may be the sum of two products:
latent attention's queries and keys are a part rebuilt from the latent
and a rotary part whose key is one head for every query head, and the
kernel reads that one head through the index map too.

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


def _attention_kernel(q_ref, k_ref, v_ref, *refs, block_q: int, block_k: int,
                      lanes: int):
    """``refs``: the rotary pair's two blocks where the call has one,
    then the output, the running maximum, sum and accumulator."""
    *rope, o_ref, m_ref, l_ref, acc_ref = refs
    qi, ki = pl.program_id(2), pl.program_id(3)
    dv = v_ref.shape[-1]
    precision = (lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    first_row, last_row = qi * block_q, qi * block_q + block_q - 1
    first_col, last_col = ki * block_k, ki * block_k + block_k - 1

    def scores(q, k):
        return lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)

    def step(masked: bool):
        v = v_ref[0, 0]
        s = scores(q_ref[0, 0], k_ref[0, 0])  # [block_q, block_k]
        if rope:
            s = s + scores(rope[0][0, 0], rope[1][0, 0])
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
        acc_ref[...] = (acc_ref[...] * jnp.tile(alpha, (1, dv // lanes))
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
        o_ref[0, 0] = (acc_ref[...] / jnp.tile(l_ref[...], (1, dv // lanes))
                       ).astype(o_ref.dtype)


def causal_attention(q, k, v, scale: float, block: int = 512,
                     dtype=jnp.bfloat16, rope=None):
    """``softmax((q k^T + q_r k_r^T) * scale + causal mask) v`` per head.

    ``q``: ``[B, T, Hq, d]``; ``k``: ``[B, T, Hkv, d]``; ``v``: ``[B, T,
    Hkv, dv]`` with ``Hq`` a multiple of ``Hkv`` (query head ``h`` reads
    key head ``h // (Hq / Hkv)``). ``rope``, where given, is a second
    pair ``(q_r [B, T, Hq, dr], k_r [B, T, Hr, dr])`` whose product is
    added to the scores, ``Hq`` a multiple of ``Hr`` (latent attention:
    one rotary key head for all). ``dtype`` is what the products round
    their operands to; ``block`` is the tile's side in positions.
    Returns float32 ``[B, T, Hq, dv]``."""
    b, t, hq, _ = q.shape
    dv = v.shape[-1]
    block = min(block, -(-t // 8) * 8)
    padded = -(-t // block) * block
    lanes = math.gcd(128, block, dv)
    n = padded // block

    def heads_first(x):  # [B, T, H, d] -> [B, H, T', d], zeros past T
        x = jnp.swapaxes(x.astype(dtype), 1, 2)
        return jnp.pad(x, ((0, 0), (0, 0), (0, padded - t), (0, 0)))

    def query_block(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def key_block(heads):
        group = hq // heads

        def index(bi, hi, qi, ki):
            # past the diagonal: the last block needed, already there
            return bi, hi // group, jnp.minimum(ki, qi), 0
        return index

    def spec(x, index):
        return pl.BlockSpec((1, 1, block, x.shape[-1]), index)

    operands = [(q * scale, query_block), (k, key_block(k.shape[2])),
                (v, key_block(v.shape[2]))]
    if rope is not None:
        operands += [(rope[0] * scale, query_block),
                     (rope[1], key_block(rope[1].shape[2]))]
    out = pl.pallas_call(
        functools.partial(_attention_kernel, block_q=block, block_k=block,
                          lanes=lanes),
        out_shape=jax.ShapeDtypeStruct((b, hq, padded, dv), jnp.float32),
        grid=(b, hq, n, n),
        in_specs=[spec(x, index) for x, index in operands],
        out_specs=pl.BlockSpec((1, 1, block, dv), query_block),
        scratch_shapes=[pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_use_interpreter(),
        name=SCOPE,
    )(*(heads_first(x) for x, _ in operands))
    return jnp.swapaxes(out[:, :, :t], 1, 2)
