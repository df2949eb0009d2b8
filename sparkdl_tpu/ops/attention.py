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

What the kernel reads and writes. A head of width ``d`` is a column of
whole lane tiles of ``[B, T, H * d]`` when ``d`` is a multiple of 128,
so an index map picks it with no copy: block ``(block, d)`` at ``(row,
block of positions, head)``. The output is written that way wherever
``dv`` allows it, ``[B, T, Hq * dv]`` in the type the caller names
(``out_dtype``): what the next product reads, with no heads-first
float32 array to cast, transpose and copy. An operand is read that way
where the caller names it (``in_place``), and ``k`` and ``v`` may then
be two columns of one array (:class:`HeadSlice`); any other operand is
cast and copied heads-first, ``[B, H, T, d]``. The tile loop is one
either way.

Products take operands of the parameters' type (bfloat16) and
accumulate in float32; the softmax's statistics are float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernel's name: what its instruction is called in the device trace
SCOPE = "attention"

#: a head is a column of whole lane tiles of ``[B, T, H * d]`` when ``d`` is
#: a multiple of this: a ``BlockSpec`` index map then picks it with no copy
_LANES = 128

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
        v = v_ref[...]
        s = scores(q_ref[...], k_ref[...])  # [block_q, block_k]
        if rope:
            s = s + scores(rope[0][...], rope[1][...])
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
        o_ref[...] = (acc_ref[...] / jnp.tile(l_ref[...], (1, dv // lanes))
                       ).astype(o_ref.dtype)


class HeadSlice(NamedTuple):
    """``x[..., start:start + width]`` of ``x`` ``[B, T, H, w]``, named and
    not taken: what :func:`causal_attention` takes for ``k`` or ``v`` when
    both lie in one array (latent attention's ``[k_nope | v]`` a head), so
    that an operand read in place is read out of ``x`` itself."""
    x: Any
    start: int
    width: int


def causal_attention(q, k, v, scale: float, block: int = 512,
                     dtype=jnp.bfloat16, rope=None, out_dtype=jnp.float32,
                     in_place: Sequence[str] = ()):
    """``softmax((q k^T + q_r k_r^T) * scale + causal mask) v`` per head.

    ``q``: ``[B, T, Hq, d]``; ``k``: ``[B, T, Hkv, d]``; ``v``: ``[B, T,
    Hkv, dv]`` with ``Hq`` a multiple of ``Hkv`` (query head ``h`` reads
    key head ``h // (Hq / Hkv)``); ``k`` and ``v`` may each be a
    :class:`HeadSlice` of a wider array. ``rope``, where given, is a
    second pair ``(q_r [B, T, Hq, dr], k_r [B, T, Hr, dr])`` whose
    product is added to the scores, ``Hq`` a multiple of ``Hr`` (latent
    attention: one rotary key head for all). ``dtype`` is what the
    products round their operands to; ``block`` is the tile's side in
    positions.

    ``out_dtype`` is what the kernel rounds its output to as it writes
    it. A caller whose next act is a product in ``dtype`` names that
    type and gets the bits that rounding the float32 output would give.

    ``in_place`` names the operands among ``"q"``, ``"k"``, ``"v"`` that
    the kernel reads where they lie: the operand's array, after the
    cast (and, for ``q``, the scale), as ``[B, T, H * w]``, with no
    heads-first copy and, for a :class:`HeadSlice`, no slice. That is
    the caller's to say, from what produces the operand: it saves a
    pass where the head arrives from a product or a slice that has
    nothing to fuse a transpose into (``models/axk1.py``), and costs one
    where XLA keeps the producer's result with the positions minor and
    the heads-first copy rides in a fusion that runs anyway
    (``models/ouro.py``). An operand that is no column of whole lane
    tiles (its width no multiple of 128, or a slice not at a multiple
    of its width) is cut and copied heads-first whatever is named.

    Returns ``out_dtype`` ``[B, T, Hq, dv]``."""
    unknown = set(in_place) - {"q", "k", "v"}
    if unknown:
        raise ValueError(f"in_place names {sorted(unknown)}: not q, k or v")
    b, t, hq, _ = q.shape
    dv = v.width if isinstance(v, HeadSlice) else v.shape[-1]
    block = min(block, -(-t // 8) * 8)
    padded = -(-t // block) * block
    lanes = math.gcd(_LANES, block, dv)
    n = padded // block

    def query_block(bi, hi, qi, ki):
        return bi, hi, qi

    def key_block(heads):
        group = hq // heads

        def index(bi, hi, qi, ki):
            # past the diagonal: the last block needed, already there
            return bi, hi // group, jnp.minimum(ki, qi)
        return index

    def heads_first(x, index):  # [B, T, H, d] -> [B, H, T', d], zeros past T
        x = jnp.pad(jnp.swapaxes(x, 1, 2),
                    ((0, 0), (0, 0), (0, padded - t), (0, 0)))
        return x, pl.BlockSpec((None, None, block, x.shape[-1]),
                               lambda *grid: (*index(*grid), 0))

    def tokens_first(x, index, start, width):  # [B, T, H, w] -> [B, T', H * w]
        columns = x.shape[-1] // width  # of `width`, a head
        x = jnp.pad(x.reshape(b, t, -1), ((0, 0), (0, padded - t), (0, 0)))

        def head_column(*grid):
            bi, hi, ti = index(*grid)
            return bi, ti, hi * columns + start // width
        return x, pl.BlockSpec((None, block, width), head_column)

    def operand(name, x, queries=False):
        x, start, width = (x if isinstance(x, HeadSlice)
                           else (x, 0, x.shape[-1]))
        index = query_block if queries else key_block(x.shape[2])
        if queries:
            x = x * scale
        if (name in in_place and width % _LANES == 0 and start % width == 0
                and x.shape[-1] % width == 0):
            return tokens_first(x.astype(dtype), index, start, width)
        return heads_first(x[..., start:start + width].astype(dtype), index)

    operands = [operand("q", q, queries=True), operand("k", k),
                operand("v", v)]
    if rope is not None:
        operands += [operand("q_r", rope[0], queries=True),
                     operand("k_r", rope[1])]
    out_tokens_first = dv % _LANES == 0
    if out_tokens_first:  # written where and as the next product reads it
        out_shape = (b, padded, hq * dv)
        out_spec = pl.BlockSpec((None, block, dv),
                                lambda bi, hi, qi, ki: (bi, qi, hi))
    else:
        out_shape = (b, hq, padded, dv)
        out_spec = pl.BlockSpec((None, None, block, dv),
                                lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    out = pl.pallas_call(
        functools.partial(_attention_kernel, block_q=block, block_k=block,
                          lanes=lanes),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        grid=(b, hq, n, n),
        in_specs=[spec for _, spec in operands],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_use_interpreter(),
        name=SCOPE,
    )(*(x for x, _ in operands))
    if out_tokens_first:
        return out[:, :t].reshape(b, t, hq, dv)
    return jnp.swapaxes(out[:, :, :t], 1, 2)
