"""A routed feed-forward over the experts this chip holds, with no
capacity and no dropped token.

The router scores every expert of the model (``route``); the layer is
told which contiguous range of them lives here (``first``, and as many
as the weight arrays hold) and computes, for every token, the weighted
sum over those of its chosen experts that fall in the range. What the
absent experts would have added is some other chip's to compute.

How many assignments an expert receives is data. Shapes are not, so
the assignments are laid out for a grouped matrix product
(:func:`grouped_layout`): sorted by expert, each expert's group
starting on a multiple of ``tile`` rows, in a buffer sized for the
worst case (every one of a token's choices held here, plus a tile of
padding an expert). A tile of rows then belongs to one expert, and the
Pallas kernel ``moe_experts`` walks the tiles: it is told each tile's
expert before the tile's turn (scalar prefetch), so the pipeline
fetches that expert's three matrices while the tile before is
computed, fetches them once for all of an expert's tiles, and skips,
without a fetch or a write, the tiles past the last one in use. The
SwiGLU (``W_d (silu(W_g x) * W_u x)``) happens in one kernel; the
hidden activations never leave VMEM. An expert whose three matrices do
not fit VMEM twice over is walked in blocks of its width (a second
grid axis), the tile's down-product carried across them in a float32
scratch; its matrices are then fetched again for every tile, so the
tiles are made long enough for the product to hide the fetch
(:func:`row_tile`).

Which of a token's choices are held here is data too, and the weighted
sum over them (the combine) is the second Pallas kernel,
``moe_combine``: it walks the tokens in blocks, reads each assignment's
row number from scalar memory, copies from the grouped result only the
rows of held assignments, one DMA a row, and sums what arrived in
VMEM. A row nobody holds is neither read nor written, and no
``[k * N, D]`` copy of the rows ever exists. So that a row is one
aligned copy, the expert kernel writes it as a slab of whole tiles of
32-bit words (:func:`slab_shape`), two bfloat16 values to a word.

Products take bfloat16 and accumulate in float32; router
probabilities and the weighted sum are float32.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the expert kernel's name, and the scope that it, the layout round it
#: and the combine carry
SCOPE = "moe_experts"
#: the combine kernel's name
COMBINE = "moe_combine"

#: tokens a grid step of the combine
_TOKEN_BLOCK = 128
#: assignments a turn of the loop that starts the copies, and words of a
#: slab a turn of the loop that sums them. The kernel is traced and
#: lowered on every run, so a doubling is paid in set-up for 0.05 to
#: 0.1 ms a layer (`tools/chip_calls/pr31_combine.py`; `PERF.md`, PR 31)
_ISSUE_UNROLL = 8
_SUM_UNROLL = 4
#: bytes of VMEM that the two copies of an expert's block of matrices may
#: take (of 128 MiB a v5e core, beside the row tiles and the products):
#: blocks of 512 columns of an expert of 7,168 x 2,048. Measured with
#: :func:`row_tile`'s 256 rows (`tools/chip_calls/pr32_experts.py`;
#: `PERF.md`, PR 32): the expert block 24.5-24.6 ms a layer, 24.8 at
#: blocks of 256 and 25.2 at blocks of 1,024
_WEIGHTS_VMEM = 48 << 20


def _use_interpreter() -> bool:
    """Off the TPU the kernel runs in Pallas's interpreter."""
    return jax.default_backend() != "tpu"


def route(logits, k: int, scoring: str = "softmax", scale: float = 1.0):
    """``(experts [N, k] int32, weights [N, k] float32)``: the ``k``
    experts with the largest float32 score over all of them (``scoring``:
    a ``softmax`` over the experts, or each one's own ``sigmoid``), their
    scores renormalised to sum to ``scale``."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        top_p, top_i = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        weights = top_p / jnp.sum(top_p, -1, keepdims=True)
    elif scoring == "sigmoid":
        top_p, top_i = lax.top_k(jax.nn.sigmoid(logits), k)
        weights = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-20)
    else:
        raise ValueError(f"unknown scoring {scoring!r}")
    return top_i.astype(jnp.int32), weights * scale


def layout_rows(assignments: int, held: int, tile: int) -> int:
    """Rows of the grouped buffer that no routing can overrun."""
    return (-(-assignments // tile) + held) * tile


def grouped_layout(experts, first: int, held: int, tile: int):
    """Where each assignment goes in the grouped buffer.

    ``experts``: ``[N, k]`` expert ids. Returns ``(row_token [R],
    dest [N, k], is_held [N, k], tile_expert [R / tile], tiles_used,
    counts [held])``: ``row_token[r]`` is the token whose copy sits in
    row ``r`` (``N`` where the row is padding); ``dest[n, j]`` is the
    row of token ``n``'s ``j``-th assignment (meaningless where
    ``is_held`` is false); ``tile_expert`` is each tile's expert,
    local to the range held, the last used tile's expert repeated
    past it; ``counts`` is the assignments each held expert got."""
    n, k = experts.shape
    a = n * k
    rows = layout_rows(a, held, tile)
    local = experts.reshape(a) - first
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32), axis=0,
                     dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    padded = -(-counts // tile) * tile
    pad_ends = jnp.cumsum(padded)
    pad_starts = pad_ends - padded
    key_sorted, order = lax.sort((key, jnp.arange(a, dtype=jnp.int32)),
                                 num_keys=1)
    group = jnp.minimum(key_sorted, held - 1)
    rank = jnp.arange(a, dtype=jnp.int32) - starts[group]
    dest_sorted = jnp.where(key_sorted < held, pad_starts[group] + rank, rows)
    row_token = jnp.full((rows,), n, jnp.int32).at[dest_sorted].set(
        order // k, mode="drop", unique_indices=True)
    dest = jnp.zeros((a,), jnp.int32).at[order].set(
        dest_sorted, unique_indices=True)
    tiles_used = pad_ends[-1] // tile
    tile_row = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    tile_expert = jnp.sum(pad_ends[None, :] <= tile_row[:, None], axis=1,
                          dtype=jnp.int32)
    last_used = jnp.maximum(tiles_used - 1, 0)
    tile_expert = jnp.minimum(tile_expert, held - 1)[
        jnp.minimum(jnp.arange(rows // tile), last_used)]
    return (row_token, dest.reshape(n, k), is_held.reshape(n, k),
            tile_expert, tiles_used.astype(jnp.int32), counts)


def slab_shape(d: int, dtype):
    """``(words, lanes)``: how :func:`grouped_swiglu` keeps a row of ``d``
    values of ``dtype``, as ``words`` sublanes of ``lanes`` 32-bit words.
    A float32 row's word ``[s, l]`` is its value ``s * lanes + l``; a
    bfloat16 row's holds the values ``2 s * lanes + l`` (low half) and
    ``(2 s + 1) * lanes + l`` (high half). ``lanes`` is 128 at the
    widths the chip sees, so a row is whole ``(8, 128)`` tiles."""
    packed = 4 // jnp.dtype(dtype).itemsize
    lanes = math.gcd(d // packed, 128)
    return d // packed // lanes, lanes


def width_block(d: int, f: int, itemsize: int) -> int:
    """Columns of an expert's width that one grid step of
    :func:`grouped_swiglu` takes: all ``f`` where two copies of the three
    matrices fit ``_WEIGHTS_VMEM``, else the largest multiple of 128
    dividing ``f`` that does."""
    fits = lambda block: 2 * 3 * d * block * itemsize <= _WEIGHTS_VMEM
    if fits(f):
        return f
    blocks = [b for b in range(128, f, 128) if f % b == 0 and fits(b)]
    if not blocks:
        raise ValueError(f"no block of an expert of {d} x {f} fits VMEM")
    return blocks[-1]


def row_tile(d: int, f: int, itemsize: int) -> int:
    """Rows of a tile of the grouped buffer. 128 where an expert's
    matrices are fetched once for all its tiles. Where the expert is
    walked in blocks of its width they are fetched again for every tile,
    and a tile's product hides its fetch only from 240 rows on (a v5e's
    197 TFLOP/s over its 819 GB/s): 256. Measured at 7,168 x 2,048, 683
    rows an expert (`tools/chip_calls/pr32_experts.py`): the kernel 5.58
    ms a layer at 256 rows and 9.2-10.6 at 128, bound by the fetch; the
    expert block whole 24.5-24.6 ms at 256 rows, 23.7-25.3 at 128 by the
    block of the width, 26.1-27.1 at 512 (`PERF.md`, PR 32)."""
    return 128 if width_block(d, f, itemsize) == f else 256


def _experts_kernel(tile_expert_ref, tiles_used_ref, x_ref, wg_ref, wu_ref,
                    wd_ref, o_ref, *acc):
    """``acc``: the float32 ``[tile, D]`` scratch that carries the
    down-product across the blocks of the expert's width (grid axis 1);
    none where the width is one block."""
    del tile_expert_ref  # read by the index maps
    if acc:  # read here: the interpreter knows no program_id inside a branch
        block, last = pl.program_id(1), pl.num_programs(1) - 1

    def store(y):
        words, lanes = o_ref.shape[1:]
        packed = o_ref.dtype != jnp.float32
        if packed:  # rounded to bfloat16, each value in the high half of a word
            y = lax.bitcast_convert_type(
                y.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32)
        part = lambda c: y[:, c * lanes:(c + 1) * lanes]
        for s in range(words):
            o_ref[:, s, :] = part(s) if not packed else lax.bitwise_or(
                part(2 * s + 1),
                lax.shift_right_logical(part(2 * s), np.uint32(16)))

    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        x = x_ref[...]
        # float32 matrices (the tests' exact mode) keep float32 products
        precision = (lax.Precision.HIGHEST if x.dtype == jnp.float32
                     else None)
        dot = lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32,
                                   precision=precision)
        gate, up = dot(x, wg_ref[0]), dot(x, wu_ref[0])
        hidden = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        y = dot(hidden, wd_ref[0])
        if not acc:
            store(y)
            return
        total, = acc

        @pl.when(block == 0)
        def _():
            total[...] = y

        @pl.when(block > 0)
        def _():
            total[...] += y

        @pl.when(block == last)
        def _():
            store(total[...])


def grouped_swiglu(x_rows, tile_expert, tiles_used, w_gate, w_up, w_down,
                   tile: int):
    """``W_d[e] (silu(W_g[e] x) * W_u[e] x)`` for every row of the
    grouped buffer ``x_rows`` (``[R, D]``), ``e`` the expert of the
    row's tile, in ``x_rows``'s precision, each row a slab of 32-bit
    words (``[R, words, lanes]``, :func:`slab_shape`; float32 for
    float32 rows, uint32 holding two bfloat16 each otherwise), which
    :func:`combine_held` copies one by one. Rows of tiles past
    ``tiles_used`` are left as they are found."""
    rows, d = x_rows.shape
    held, _, f = w_gate.shape
    slab = slab_shape(d, x_rows.dtype)
    word = jnp.float32 if x_rows.dtype == jnp.float32 else jnp.uint32
    itemsize = w_gate.dtype.itemsize
    block = width_block(d, f, itemsize)

    def row_block(t, b, tile_expert, tiles_used):
        # a tile past the last one in use names the last one's block:
        # nothing is fetched for it and nothing written
        return jnp.minimum(t, jnp.maximum(tiles_used[0] - 1, 0)), 0

    def slab_block(t, b, tile_expert, tiles_used):
        return (*row_block(t, b, tile_expert, tiles_used), 0)

    def width(t, b, tiles_used):
        # past the last tile in use: the block already there
        return jnp.where(t < tiles_used[0], b, f // block - 1)

    def in_block(t, b, tile_expert, tiles_used):
        return tile_expert[t], 0, width(t, b, tiles_used)

    def out_block(t, b, tile_expert, tiles_used):
        return tile_expert[t], width(t, b, tiles_used), 0

    return pl.pallas_call(
        _experts_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, *slab), word),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile, f // block),
            in_specs=[pl.BlockSpec((tile, d), row_block),
                      pl.BlockSpec((1, d, block), in_block),
                      pl.BlockSpec((1, d, block), in_block),
                      pl.BlockSpec((1, block, d), out_block)],
            out_specs=pl.BlockSpec((tile, *slab), slab_block),
            scratch_shapes=([] if block == f
                            else [pltpu.VMEM((tile, d), jnp.float32)])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two copies of a block of an expert's matrices and of the row
            # tiles, the down-product and what carries it
            vmem_limit_bytes=int(2 * 3 * d * block * itemsize
                                 + 8 * tile * d * 4 + (8 << 20))),
        interpret=_use_interpreter(),
        name=SCOPE,
    )(tile_expert, tiles_used.reshape(1), x_rows, w_gate, w_up, w_down)


def _loop(n, unroll, body):
    """``body(i)`` for ``i`` in ``range(n)``, ``unroll`` to a turn."""
    def turn(i, carry):
        for u in range(unroll):
            body(lax.add(lax.mul(i, np.int32(unroll)), np.int32(u)))
        return carry
    lax.fori_loop(0, n // unroll, turn, 0)


def _combine_kernel(copies_ref, dest_ref, dest_rows_ref, w_ref, y_ref, o_ref,
                    buf, sem):
    """One block of ``TB`` tokens. ``dest_ref`` (scalar memory; its first
    ``k * TB`` words, choice by choice) and ``dest_rows_ref`` (``[TB,
    k]``): each assignment's row of ``y_ref``, negative where it is not
    held; ``w_ref`` (``[TB, k]``): its weight; ``copies_ref[step]``: how
    many of the block's are held. ``buf`` (``[k * TB * words, lanes]``)
    receives their slabs, an assignment's at its place in ``dest_ref``.

    The body is traced and lowered on every run, before the compile
    cache is asked, so it is written in ``lax`` primitives over numpy
    constants: a ``jnp`` function or an operator on a traced value
    costs two to three times as much to trace."""
    tb, k = w_ref.shape
    words, lanes = y_ref.shape[1:]
    packed = y_ref.dtype != jnp.float32
    tile = (8, lanes)
    times = lambda i, n: lax.mul(i, np.int32(n))

    def row_copy(row, a):
        return pltpu.make_async_copy(
            y_ref.at[row], buf.at[pl.ds(times(a, words), words)], sem)

    def start(a):
        row = dest_ref[a]

        @pl.when(lax.ge(row, np.int32(0)))
        def _():
            # the kernel is compiled without bounds checks
            row_copy(lax.min(row, np.int32(y_ref.shape[0] - 1)), a).start()
    _loop(k * tb, _ISSUE_UNROLL, start)
    _loop(copies_ref[pl.program_id(0)], 1,
          lambda _: row_copy(np.int32(0), np.int32(0)).wait())

    def sum_rows(group):
        # eight tokens at a time: a tile of words is one sublane of each
        # one's slab, and of the answer a tile of 8 rows
        n0 = pl.multiple_of(times(group, 8), 8)
        column = lambda x, j: lax.broadcast_in_dim(
            lax.slice_in_dim(x, j, j + 1, axis=1), tile, (0, 1))
        held = lax.ge(dest_rows_ref[pl.ds(n0, 8), :], np.int32(0))
        w = w_ref[pl.ds(n0, 8), :]
        held = [column(held, j) for j in range(k)]
        w = [column(w, j) for j in range(k)]
        zero = lax.broadcast(np.float32(0), tile)
        if packed:
            low = lax.full(tile, 16, np.uint32)
            high = lax.full(tile, 0xFFFF0000, np.uint32)

        def word_tiles(s):
            sums = None
            for j in range(k):
                first = lax.add(times(lax.add(n0, np.int32(j * tb)), words), s)
                word = buf[pl.ds(first, 8, stride=words), :]
                values = [word] if not packed else [
                    lax.bitcast_convert_type(lax.shift_left(word, low), jnp.float32),
                    lax.bitcast_convert_type(lax.bitwise_and(word, high), jnp.float32)]
                # selected, never multiplied by a zero weight: a slab that
                # was not copied holds whatever an earlier block left there
                terms = [lax.select(held[j], lax.mul(w[j], v), zero) for v in values]
                sums = terms if sums is None else [
                    lax.add(a, b) for a, b in zip(sums, terms)]
            width = len(sums) * lanes
            o_ref[pl.ds(n0, 8), pl.ds(pl.multiple_of(times(s, width), width), width)] = (
                lax.concatenate(sums, 1))
        _loop(words, math.gcd(words, _SUM_UNROLL), word_tiles)
    _loop(tb // 8, 1, sum_rows)


@jax.jit  # traced and lowered once for all the layers of a program
def combine_held(y_rows, dest, is_held, weights):
    """``y[n] = sum over j with is_held[n, j] of weights[n, j] *
    row dest[n, j] of y_rows`` (``[N, D]`` float32, summed in ascending
    ``j``).

    ``y_rows``: ``[R, words, lanes]`` from :func:`grouped_swiglu`;
    ``dest``, ``is_held``, ``weights``: ``[N, k]``. Only the rows of
    held assignments are read, each by a copy of its own; what the other
    rows hold, and ``dest`` where ``is_held`` is false, is never looked
    at. A held ``dest`` past the last row reads the last row."""
    n, k = dest.shape
    _, words, lanes = y_rows.shape
    d = words * lanes * (1 if y_rows.dtype == jnp.float32 else 2)
    tb = _TOKEN_BLOCK
    steps = -(-n // tb)
    pad = ((0, steps * tb - n), (0, 0))
    dest = jnp.pad(jnp.where(is_held, dest, -1), pad, constant_values=-1)
    weights = jnp.pad(weights.astype(jnp.float32), pad)
    copies = jnp.sum(dest.reshape(steps, tb * k) >= 0, axis=1, dtype=jnp.int32)
    # a block of a flat array in scalar memory is a multiple of 1,024 words
    chunk = -(-k * tb // 1024) * 1024
    by_choice = jnp.pad(
        jnp.swapaxes(dest.reshape(steps, tb, k), 1, 2).reshape(steps, k * tb),
        ((0, 0), (0, chunk - k * tb)))
    by_token = pl.BlockSpec((tb, k), lambda i, copies: (i, 0))
    y = pl.pallas_call(
        _combine_kernel,
        out_shape=jax.ShapeDtypeStruct((steps * tb, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[pl.BlockSpec((chunk,), lambda i, copies: (i,),
                                   memory_space=pltpu.SMEM),
                      by_token, by_token,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tb, d), lambda i, copies: (i, 0)),
            scratch_shapes=[pltpu.VMEM((k * tb * words, lanes), y_rows.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True,
            vmem_limit_bytes=int(k * tb * words * lanes * 4 + 4 * tb * d * 4
                                 + (8 << 20))),
        interpret=_use_interpreter(),
        name=COMBINE,
    )(copies, by_choice.reshape(-1), dest, weights, y_rows)
    return y[:n]


def held_experts_ffn(x, experts, weights, w_gate, w_up, w_down, first: int,
                     tile: Optional[int] = None):
    """The held experts' part of the routed feed-forward.

    ``x``: ``[N, D]``; ``experts``, ``weights``: ``[N, k]`` from
    :func:`route`; ``w_gate``, ``w_up``: ``[held, D, F]``, ``w_down``:
    ``[held, F, D]``, the matrices of experts ``first .. first +
    held``. Returns ``(y [N, D] float32, counts [held] int32)``:
    ``y[n] = sum over j with experts[n, j] held of weights[n, j] *
    expert(x[n])``, and the assignments each held expert received.
    ``tile`` is the rows of a tile of the grouped buffer
    (:func:`row_tile` where not given)."""
    with jax.named_scope(SCOPE):
        d = x.shape[1]
        held, _, f = w_gate.shape
        tile = tile or row_tile(d, f, w_gate.dtype.itemsize)
        (row_token, dest, is_held, tile_expert, tiles_used,
         counts) = grouped_layout(experts, first, held, tile)
        x = x.astype(w_gate.dtype)
        x_rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[row_token]
        y_rows = grouped_swiglu(x_rows, tile_expert, tiles_used, w_gate, w_up,
                                w_down, tile)
        return combine_held(y_rows, dest, is_held, weights), counts


def record_routing(routing, assignments: int,
                   registry: Optional[Any] = None) -> None:
    """Sum a model's ``routing`` output (``[rows, layers, 1 + held]``,
    or ``[layers, 1 + held]`` already summed over rows; a row a layer
    that routes) into the registry. ``assignments`` is what the caller
    knows and the output does not say: every assignment those rows made,
    held here or not (rows x tokens x experts per token x layers that
    route). Adds it to the counter ``moe.assignments`` and the held ones
    to ``moe.assignments_held``; sets the gauge ``moe.expert_load_max``
    to the most that one held expert of one layer received from these
    rows."""
    from sparkdl_tpu.obs.registry import default_registry
    reg = registry or default_registry()
    routing = np.asarray(routing)
    if routing.ndim == 3:
        routing = routing.sum(axis=0)
    reg.counter("moe.assignments").add(int(assignments))
    reg.counter("moe.assignments_held").add(int(routing[:, 0].sum()))
    reg.gauge("moe.expert_load_max").set(int(routing[:, 1:].max()))
