"""The gated delta rule of a linear-attention layer, computed in chunks.

Per head, with a state ``S`` of ``[dk, dv]`` that is zero before the
first position, the rule reads, position by position::

    S   <- exp(g_t) * S
    d_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

Run that way it is one tiny matrix-vector step per position: 8,192
dependent steps a row. :func:`gated_delta_rule` computes the same
numbers a chunk of ``C`` positions at a time (the WY form of Yang et
al., "Gated Delta Networks", 2024): inside a chunk the ``C`` rank-one
updates are folded into one ``C x C`` lower-triangular system, and only
the chunk-to-chunk recurrence, a few ``[C, d]`` products a chunk, stays
sequential. Sequences come heads first (``[B, H, T, d]``), so that
cutting them into chunks moves no data. q and k may have fewer heads
than v (value head ``h`` reads key head ``h // (Hv / Hk)``), and all
three may be head ranges of one array (:class:`Heads`): the kernel's
index maps pick the heads where they lie, so no key head is repeated
and no range is cut out and copied.

All of it is one Pallas kernel, ``gdn_scan``. The grid walks (row,
group of heads, block of positions). A block is a few tiles; a tile is
as many whole chunks as fill the array's 128 lanes (two chunks of 64).
A tile's chunks lie side by side as one wide matrix ``[C, 128]`` where
they stand on the left of a product and as one block-diagonal matrix
``[128, 128]`` where they stand on its right, so that every product of
the chunks' systems is at the array's full width and streams ``C`` rows
for all of a tile's chunks at once.

What lives in VMEM only, per tile: the decay mask, the system's matrix
``m``, its powers and the inverse ``(I - m)^-1``, the right-hand sides
``u`` and ``w``, the decayed lower triangle of ``q k^T``. The inverse is
the Neumann series of a nilpotent matrix in its factored form,
``(I + m)(I + m^2)(I + m^4)...``: ten products for a chunk of 64. What
is sequential: the ``[dk, dv]`` float32 state of each head, a VMEM
scratch carried along the block axis (``"arbitrary"``) and from chunk to
chunk inside a block; four ``[C, d]`` products a chunk. What crosses the
chip's memory: q, k, v and the two gates in, o out, once each (a key
head once for each grid step's group of value heads that reads it).

Every stage of the chunk-local work is written out for all the tiles
and heads of a grid step before the next stage, and the heads'
recurrences chunk by chunk side by side: one tile's chain of dependent
products would leave the units waiting, eight chains fill them.

Precision. The gates, the decays, the triangular system and the state
are float32. The system's own products (``k_beta k^T``, the squarings,
the two right-hand sides) split each float32 operand into a bfloat16
head and tail and take three bfloat16 passes (head x head, tail x head,
head x tail: what ``Precision.HIGH`` is, an error of about 2^-16 where
one pass gives 2^-8), because their result multiplies everything a
chunk writes. The ``[C, d]`` products (``q k^T``, ``w S``, ``q S``,
``qk v_new``, ``k^T v_new``) round their operands to the parameters'
type (bfloat16; float32 makes them exact) and accumulate in float32, as
every matrix product of the model does. The series is only as stable as
its powers are small: keys that lean on one direction within a chunk
(a mean cosine of 0.5) make them cancel beyond float32's reach, in this
kernel as in any float32 form of the series.

Everything here runs under the scope ``gdn_scan``, and the kernel's
instruction carries the same name in a device trace.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the scope every instruction of the rule carries in its ``op_name``,
#: and the kernel's name
SCOPE = "gdn_scan"

#: the array's width in lanes: a tile holds as many whole chunks as fit
_LANES = 128
#: tiles and heads to a grid step. Every stage of the chunk-local work
#: is written out for all of them before the next stage, so that the
#: scheduler has eight independent chains to fill the units with; the
#: heads' recurrences, each sequential, run side by side
_TILES = 2
_HEADS = 4

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _use_interpreter() -> bool:
    """Off the TPU the kernel runs in Pallas's interpreter."""
    return jax.default_backend() != "tpu"


def _split(x):
    """A float32 matrix as a bfloat16 head and tail."""
    head = x.astype(jnp.bfloat16)
    return head, (x - head.astype(_F32)).astype(jnp.bfloat16)


def _dot3(a, b, dims=_NN):
    """The product of two split matrices in three bfloat16 passes,
    accumulated in float32 (the tail x tail term, 2^-16 of the result,
    is left out, as ``Precision.HIGH`` leaves it out)."""
    (a_head, a_tail), (b_head, b_tail) = a, b
    dot = functools.partial(lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=_F32)
    return dot(a_head, b_head) + dot(a_tail, b_head) + dot(a_head, b_tail)


def _gdn_kernel(q_ref, k_ref, v_ref, beta_ref, gc_ref, o_ref, state_ref, *,
                chunk: int, tile: int, tiles: int, heads: int, ratio: int,
                dtype):
    precision = lax.Precision.HIGHEST if dtype == _F32 else None

    def dot(a, b, dims=_NN):
        return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=_F32,
                               precision=precision)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, _F32)

    # A tile's chunks lie side by side in a wide matrix [chunk, tile]:
    # row i, lane l is entry (i, l % chunk) of chunk l // chunk
    per = tile // chunk
    rows = lax.broadcasted_iota(jnp.int32, (chunk, tile), 0)
    lanes = lax.broadcasted_iota(jnp.int32, (chunk, tile), 1)
    which = lanes // chunk
    cols = lanes - which * chunk
    lower, strict = cols <= rows, cols < rows
    eye = (rows == cols).astype(_F32)

    def wide(x):
        """The diagonal blocks of ``[tile, tile]`` side by side."""
        out = x[:chunk]
        for c in range(1, per):
            out = jnp.where(which == c, x[c * chunk:(c + 1) * chunk], out)
        return out

    def diagonal(x):
        """And back: a wide matrix times this is every chunk's own
        product at once, at the array's full width."""
        return jnp.concatenate(
            [jnp.where(which == c, x, 0.0) for c in range(per)], axis=0)

    # every (head, tile) of the block: a stage is a list over them
    each = [(hh, i) for i in range(tiles) for hh in range(heads)]

    def tile_of(ref, hh, i):
        return ref[0, hh, pl.ds(i * tile, tile), :]

    def columns(ref):
        """The gates come a tile to a row; a position's own are a
        column ``[tile, 1]``."""
        turned = [ref[0, hh, 0].T for hh in range(heads)]
        return [turned[hh][:, i:i + 1] for hh, i in each]

    beta, gc = columns(beta_ref), columns(gc_ref)
    # the block holds the key heads of the step's value heads: head hh
    # reads key head hh // ratio of it
    q, k = ([tile_of(ref, hh // ratio, i) for hh, i in each]
            for ref in (q_ref, k_ref))
    # exp(gc_i - gc_j) for j <= i; the mask goes on the exponent so
    # that the upper half never overflows
    decay = [jnp.exp(jnp.where(
        lower, wide(jnp.broadcast_to(x, (tile, tile)))
        - gc_ref[0, hh, 0, i:i + 1, :], -jnp.inf))
        for x, (hh, i) in zip(gc, each)]
    k_beta = [x * b for x, b in zip(k, beta)]
    m = [jnp.where(strict, -wide(_dot3(_split(kb), _split(x), _NT)) * d, 0.0)
         for kb, x, d in zip(k_beta, k, decay)]
    # (I - m)^-1: m is nilpotent (m^chunk = 0), so the Neumann series
    # ends, and it factors into log2(chunk) squarings
    inv, power = [eye + x for x in m], m
    right = [_split(diagonal(x)) for x in m]
    reach = 2
    while reach < chunk:
        power = [_dot3(_split(p), r) for p, r in zip(power, right)]
        right = [_split(diagonal(p)) for p in power]
        inv = [x + _dot3(_split(x), r) for x, r in zip(inv, right)]
        reach *= 2
    solve = [_split(diagonal(x)) for x in inv]
    grow = [jnp.exp(x) for x in gc]
    u = [_dot3(s, _split(tile_of(v_ref, hh, i) * b))
         for s, b, (hh, i) in zip(solve, beta, each)]
    w = [_dot3(s, _split(kb * e)) for s, kb, e in zip(solve, k_beta, grow)]
    qk = [jnp.where(lower, wide(dot(x, y, _NT)) * d, 0.0)
          for x, y, d in zip(q, k, decay)]
    q_in = [x * e for x, e in zip(q, grow)]

    # what is sequential: chunk after chunk, the heads side by side
    states = [state_ref[hh] for hh in range(heads)]
    for i in range(tiles):
        for c in range(per):
            first, end = c * chunk, (c + 1) * chunk
            for hh in range(heads):
                j, state = i * heads + hh, states[hh]  # as in `each`
                g_last = gc[j][end - 1:end]  # [1, 1]: the chunk's whole decay
                v_new = u[j][first:end] - dot(w[j][first:end], state)
                o_ref[0, hh, pl.ds(i * tile + first, chunk), :] = (
                    dot(q_in[j][first:end], state)
                    + dot(qk[j][:, first:end], v_new))
                k_out = k[j][first:end] * jnp.exp(g_last - gc[j][first:end])
                carried = jnp.exp(
                    jnp.broadcast_to(g_last, (1, state.shape[1])))
                states[hh] = state * carried + dot(k_out, v_new, _TN)
    for hh in range(heads):
        state_ref[hh] = states[hh]


class Heads(NamedTuple):
    """``x[:, start:start + count]`` of a heads-first ``x`` ``[B, H, T,
    d]``, named and not taken: what :func:`gated_delta_rule` takes for
    ``q``, ``k`` or ``v`` when they lie in one array (the convolution's
    ``[q | k | v]``), so that the kernel reads each out of ``x`` itself."""
    x: Any
    start: int
    count: int


def _heads(x) -> Heads:
    return x if isinstance(x, Heads) else Heads(x, 0, x.shape[1])


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     dtype=jnp.bfloat16):
    """``o`` of the rule above for whole sequences, heads first.

    ``q``, ``k``: ``[B, Hk, T, dk]`` (``k`` L2-normalised, ``q`` already
    scaled); ``v``: ``[B, Hv, T, dv]``, ``Hv`` a multiple of ``Hk``
    (value head ``h`` reads key head ``h // (Hv / Hk)``, the order of
    ``jnp.repeat``) whose ratio divides, or is a multiple of, the value
    heads of a grid step; each may be a :class:`Heads` range of a wider
    array. ``g`` (log-decay, at most 0) and ``beta``: ``[B, Hv, T]``.
    Returns float32 ``[B, Hv, T, dv]``. ``T`` need not be a multiple of
    ``chunk``: the tail is padded with positions that write nothing
    (``beta = 0``, ``g = 0``) and cut off again. ``dtype`` is what the
    ``[C, d]`` products round their operands to (the parameters'
    storage type; float32 makes them exact).

    The kernel reads an operand where it lies when the range starts at
    a whole block of its heads and ``T`` needs no padding; otherwise
    that operand alone is cut out (and padded)."""
    with jax.named_scope(SCOPE):
        q, k, v = (_heads(x) for x in (q, k, v))
        b, _, t, dk = q.x.shape
        dv, h, hk = v.x.shape[-1], v.count, q.count
        heads = math.gcd(h, _HEADS)  # value heads a grid step
        ratio = h // hk
        if k.count != hk or h % hk or (heads % ratio and ratio % heads):
            raise ValueError(
                f"{h} value heads in steps of {heads} cannot share {hk} query "
                f"and {k.count} key heads evenly: repeat the keys")
        tile = chunk * max(1, _LANES // chunk)
        tiles = min(_TILES, -(-t // tile))
        block = tiles * tile
        pad = (-t) % block
        n = (t + pad) // block

        def padded(x):
            return jnp.pad(x.astype(_F32),
                           ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))

        def operand(x, share):
            """The array the kernel reads and its spec, for heads that
            ``share`` value heads each read: a grid step's value heads
            read ``width`` of them, one block."""
            width = max(1, heads // share)
            array, start = x.x, x.start
            if pad or start % width:  # cut out, as the heads' own array
                array, start = padded(array[:, start:start + x.count]), 0

            def index(bi, hi, ni):
                first = hi * heads // (share * width)
                return bi, first + start // width, ni, 0
            return array.astype(_F32), pl.BlockSpec(
                (1, width, block, array.shape[-1]), index)

        g, beta = (padded(x) for x in (g, beta))
        # decay from the chunk's start, own step included
        gc = jnp.cumsum(g.reshape(b, h, -1, chunk), axis=-1)
        beta, gc = (x.reshape(b, h, n, tiles, tile) for x in (beta, gc))

        def gates(bi, hi, ni):
            return bi, hi, ni, 0, 0

        operands = [operand(q, ratio), operand(k, ratio), operand(v, 1)]
        o = pl.pallas_call(
            functools.partial(_gdn_kernel, chunk=chunk, tile=tile, tiles=tiles,
                              heads=heads, ratio=ratio, dtype=jnp.dtype(dtype)),
            out_shape=jax.ShapeDtypeStruct((b, h, t + pad, dv), _F32),
            grid=(b, h // heads, n),
            in_specs=[spec for _, spec in operands]
            + [pl.BlockSpec((1, heads, 1, tiles, tile), gates)] * 2,
            out_specs=pl.BlockSpec((1, heads, block, dv),
                                   lambda bi, hi, ni: (bi, hi, ni, 0)),
            scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_use_interpreter(),
            name=SCOPE,
        )(*(x for x, _ in operands), beta, gc)
        return o[:, :, :t]
