"""A routed feed-forward over the experts this chip holds, with no
capacity and no dropped token.

The router scores every expert of the model (``route``); the layer is
told which contiguous range of them lives here (``first``, and as many
as the weight arrays hold) and computes, for every token, the weighted
sum over those of its chosen experts that fall in the range. What the
absent experts would have added is some other chip's to compute.

How many assignments an expert receives is data. Shapes are not, so
the assignments are laid out for a grouped matrix product
(:func:`grouped_layout`): expert by expert, inside an expert's group in
the order they come, each expert's group starting on a multiple of
``tile`` rows, in a layout sized for the worst case (every one of a
token's choices held here, plus a tile of padding an expert). An
assignment's place in its group is the number of earlier assignments
to the same expert, and that is counted on the MXU (ones times a
triangle of ones, block by block) where a sort would move every key;
of the layout only the scatter of the tokens to their rows touches the
assignments one by one. A tile of rows then belongs to one expert, and
the Pallas kernel ``moe_experts`` walks the tiles: it is told each
tile's expert before the tile's turn (scalar prefetch), so the pipeline
fetches that expert's three matrices while the tile before is
computed, fetches them once for all of an expert's tiles, and skips,
without a fetch or a write, the tiles past the last one in use. No
grouped copy of the tokens' rows exists: the kernel reads a tile's
tokens from scalar memory and copies their rows out of ``x`` itself,
one DMA a row, the next tile's while this one is computed, so only the
rows of tiles in use ever move. A single row of a tiled ``[N, D]``
array is no copy the chip makes, so the rows are first laid out as
slabs of whole 32-bit words (``moe_slabs``, :func:`row_slabs`), which
takes the place of their cast to the matrices' type. The SwiGLU
(``W_d (silu(W_g x) * W_u x)``) happens in one kernel; the hidden
activations never leave VMEM. An expert whose three matrices do not
fit VMEM twice over is walked in blocks of its width (a second grid
axis), the tile's down-product carried across them in a float32
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
aligned copy, the expert kernel writes it, too, as a slab of 32-bit
words (:func:`slab_shape`), two bfloat16 values to a word.

Products take bfloat16 and accumulate in float32; router
probabilities and the weighted sum are float32.
"""

from __future__ import annotations

import functools
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
#: the name of the kernel that lays the tokens' rows out as slabs
SLABS = "moe_slabs"

#: tokens a grid step of the combine
_TOKEN_BLOCK = 128
#: tokens a grid step of `moe_slabs`: 64, 256 and 512 read the same to 0.5%
#: (`tools/chip_calls/pr33_gather.py`; `PERF.md`, PR 33)
_SLAB_ROWS = 128
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
#: assignments a block of :func:`grouped_layout`'s count. One size for
#: both shapes the tree runs: XLA lays the ones out experts-major where
#: the experts are few, so 12 of them cost 12 sublanes and not 128 lanes.
#: Measured (`tools/chip_calls/pr37_layout.py`; `PERF.md`, PR 37): the
#: layout whole 0.970 ms at 256, 0.994 at 128 and 1.018 at 512 over
#: 163,840 assignments to 128 experts held, the count 0.08 ms of it and
#: the scatter 0.76; 0.72-0.74 at all three over 131,072 to 12
_COUNT_BLOCK = 256


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
    row of token ``n``'s ``j``-th assignment (``R`` where ``is_held``
    is false); ``tile_expert`` is each tile's expert, local to the
    range held, the last used tile's expert repeated past it;
    ``counts`` is the assignments each held expert got.

    Inside its expert's group an assignment keeps its place among the
    assignments (token by token, choice by choice), which is how many
    earlier ones chose the same expert. That is counted, and nothing
    is sorted: the assignments are cut into blocks of ``_COUNT_BLOCK``,
    each an ``[held, block]`` matrix of zeros with a one where the
    assignment chose the expert (the assignments on the lanes, so that
    12 experts do not cost what 128 do); the matrix times a triangle
    of ones counts the earlier ones inside the block (bfloat16
    ones, float32 sums: whole numbers under 2^24, exact), a running sum
    over the blocks' totals those of the blocks before, and the row is
    the sum over the experts of the matrix times (both counts + where
    the expert's group starts), which takes the place of a lookup in a
    table by expert. One scatter is left, of the tokens to their
    rows."""
    n, k = experts.shape
    a = n * k
    rows = layout_rows(a, held, tile)
    local = experts.reshape(a) - first
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).astype(jnp.int32)
    block = _COUNT_BLOCK
    blocks = -(-a // block)
    # chose[b, e, i]: assignment i of block b chose held expert e
    chose = (jnp.pad(key, (0, blocks * block - a), constant_values=held)
             .reshape(blocks, 1, block)
             == jnp.arange(held, dtype=jnp.int32)[None, :, None])
    earlier = jnp.triu(jnp.ones((block, block), jnp.bfloat16), 1)  # [j, i]: j < i
    in_block = jnp.einsum("bej,ji->bei", chose.astype(jnp.bfloat16), earlier,
                          preferred_element_type=jnp.float32)
    totals = jnp.sum(chose, axis=2, dtype=jnp.float32)
    through = jnp.cumsum(totals, axis=0)
    before = through - totals
    counts = through[-1].astype(jnp.int32)
    padded = -(-counts // tile) * tile
    pad_ends = jnp.cumsum(padded)
    pad_starts = pad_ends - padded
    group_row = before + pad_starts.astype(jnp.float32)
    dest = jnp.sum(jnp.where(chose, in_block + group_row[:, :, None], 0.0), axis=1)
    dest = jnp.where(is_held, dest.reshape(-1)[:a].astype(jnp.int32), rows)
    row_token = jnp.full((rows,), n, jnp.int32).at[dest].set(
        jnp.arange(a, dtype=jnp.int32) // k, mode="drop", unique_indices=True)
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


def slab_word(dtype):
    """The 32-bit type of a slab's words: float32 rows keep theirs, two
    bfloat16 values share a uint32."""
    return jnp.float32 if jnp.dtype(dtype) == jnp.float32 else jnp.uint32


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


def _times(i, n: int):
    """``i * n`` in the primitives a kernel's loops are written in."""
    return lax.mul(i, np.int32(n))


def _loop(n, unroll, body):
    """``body(i)`` for ``i`` in ``range(n)``, ``unroll`` to a turn."""
    def turn(i, carry):
        for u in range(unroll):
            body(lax.add(_times(i, unroll), np.int32(u)))
        return carry
    lax.fori_loop(0, n // unroll, turn, 0)


def _store_slabs(o_ref, y, packed: bool):
    """``y`` (``[rows, D]`` float32) into ``o_ref`` (``[rows, words,
    lanes]``), each row a slab (:func:`slab_shape`): as it is, or
    (``packed``) rounded to bfloat16, two values to a uint32."""
    words, lanes = o_ref.shape[1:]
    if packed:  # rounded to bfloat16, each value in the high half of a word
        y = lax.bitcast_convert_type(
            y.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32)
    part = lambda c: y[:, c * lanes:(c + 1) * lanes]
    for s in range(words):
        o_ref[:, s, :] = part(s) if not packed else lax.bitwise_or(
            part(2 * s + 1),
            lax.shift_right_logical(part(2 * s), np.uint32(16)))


def _slabs_kernel(x_ref, o_ref):
    _store_slabs(o_ref, x_ref[...].astype(jnp.float32),
                 packed=o_ref.dtype != jnp.float32)


def row_slabs(x, dtype):
    """``x`` (``[N, D]``) cast to ``dtype`` (float32 or bfloat16), every
    row a slab of 32-bit words (``[N, words, lanes]``, :func:`slab_shape`:
    float32, or uint32 holding two bfloat16 each), so that a row is one
    aligned copy. A Pallas kernel (``moe_slabs``), because XLA's own
    fusion for it takes twice as long: 3.93 against 1.91 ms at 16,384 x
    7,168 from float32 and 0.73 against 0.36 at 16,384 x 2,048, where
    the cast alone takes 1.14 and 0.38
    (`tools/chip_calls/pr33_gather.py`; `PERF.md`, PR 33)."""
    n, d = x.shape
    slab = slab_shape(d, dtype)
    word = slab_word(dtype)
    tb = min(_SLAB_ROWS, -(-n // 8) * 8)
    return pl.pallas_call(
        _slabs_kernel,
        out_shape=jax.ShapeDtypeStruct((n, *slab), word),
        grid=(-(-n // tb),),
        in_specs=[pl.BlockSpec((tb, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tb, *slab), lambda i: (i, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(8 * tb * d * 4 + (8 << 20))),
        interpret=_use_interpreter(),
        name=SLABS,
    )(x)


def _experts_kernel(blocks, tile_expert_ref, tiles_used_ref, tokens_ref,
                    next_tokens_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, slabs,
                    sems, x_tile, *acc):
    """One tile of rows against one of the ``blocks`` blocks of its
    expert's width.

    ``x_ref`` (``[N, words, lanes]``, wherever the tokens' rows lie): the
    tile's rows are copied from it, one DMA a row, the row of token
    ``tokens_ref[...]`` to row ``r`` of the tile's slot in ``slabs``
    (``[2 * tile * words, lanes]``: two slots of ``tile`` slabs;
    ``sems[slot]`` counts a slot's copies).
    The tile after this one's are started, through ``next_tokens_ref``,
    into the other slot while this one is computed, an equal share of
    them at each block of the width: a block's step then waits for no
    more of them than for its matrices. ``tokens_ref`` and
    ``next_tokens_ref`` (scalar memory) hold the ``row_token`` of whole
    tiles, this tile's among them and the next one's. ``x_tile``
    (``[tile, D]``) is the tile as a matrix, unpacked once for all the
    blocks. ``acc``: the float32 ``[tile, D]`` scratch that carries the
    down-product across those blocks (grid axis 1); none where the width
    is one block.

    The loops that start and await the copies are traced and lowered on
    every run (see :func:`_combine_kernel`): ``lax`` primitives over
    numpy constants."""
    del tile_expert_ref  # read by the index maps
    # read here: the interpreter knows no program_id inside a branch
    t, block = pl.program_id(0), pl.program_id(1)
    used = tiles_used_ref[0]
    tile = x_tile.shape[0]
    words, lanes = x_ref.shape[1:]
    packed = slabs.dtype != jnp.float32
    chunk_tiles = tokens_ref.shape[0] // tile
    slot_of = lambda tile_index: lax.rem(tile_index, np.int32(2))

    def row_copy(token, slot, r):
        first = _times(lax.add(_times(slot, tile), r), words)
        return pltpu.make_async_copy(
            x_ref.at[token], slabs.at[pl.ds(first, words)], sems.at[slot])

    def fetch(tokens, tile_index, first_row, rows):
        """Start the copies of ``rows`` rows of tile ``tile_index`` from
        ``first_row`` on."""
        slot = slot_of(tile_index)
        in_chunk = _times(lax.rem(tile_index, np.int32(chunk_tiles)), tile)
        unroll = math.gcd(rows, _ISSUE_UNROLL)

        def turn(i, carry):
            # a turn's tokens are read before its first copy starts: a read
            # after a start waits for scalar memory's whole latency
            turn_rows = [lax.add(first_row, lax.add(_times(i, unroll), np.int32(u)))
                         for u in range(unroll)]
            named = [tokens[lax.add(in_chunk, r)] for r in turn_rows]
            for token, r in zip(named, turn_rows):
                row_copy(token, slot, r).start()
            return carry
        lax.fori_loop(0, rows // unroll, turn, 0)

    in_use = t < used
    # the next tile's copies, an equal share at each block where they divide
    share, shares = (tile // blocks, blocks) if tile % blocks == 0 else (tile, 1)

    @pl.when(in_use & (t + 1 < used) & (block < shares))
    def _():
        fetch(next_tokens_ref, t + 1, _times(block, share), share)

    @pl.when(in_use & (block == 0))
    def _():
        @pl.when(t == 0)
        def _():
            fetch(tokens_ref, t, np.int32(0), tile)
        slot = slot_of(t)
        _loop(tile, math.gcd(tile, _ISSUE_UNROLL),
              lambda _: row_copy(np.int32(0), slot, np.int32(0)).wait())
        base = _times(slot, tile * words)
        column = lambda c: pl.ds(c * lanes, lanes)
        for s in range(words):
            # word `s` of every slab: a row of the tile a sublane
            word = slabs[pl.ds(lax.add(base, np.int32(s)), tile, stride=words), :]
            if not packed:
                x_tile[:, column(s)] = word
                continue
            for c, half in ((2 * s, lax.shift_left(word, np.uint32(16))),
                            (2 * s + 1, lax.bitwise_and(word, np.uint32(0xFFFF0000)))):
                x_tile[:, column(c)] = lax.bitcast_convert_type(
                    half, jnp.float32).astype(x_tile.dtype)

    @pl.when(in_use)
    def _():
        x = x_tile[...]
        # float32 matrices (the tests' exact mode) keep float32 products
        precision = (lax.Precision.HIGHEST if x.dtype == jnp.float32
                     else None)
        dot = lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32,
                                   precision=precision)
        gate, up = dot(x, wg_ref[0]), dot(x, wu_ref[0])
        hidden = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        y = dot(hidden, wd_ref[0])
        if not acc:
            _store_slabs(o_ref, y, packed)
            return
        total, = acc

        @pl.when(block == 0)
        def _():
            total[...] = y

        @pl.when(block > 0)
        def _():
            total[...] += y

        @pl.when(block == blocks - 1)
        def _():
            _store_slabs(o_ref, total[...], packed)


@functools.partial(jax.jit, static_argnames="tile")  # one trace for all the layers
def grouped_swiglu(x, row_token, tile_expert, tiles_used, w_gate, w_up, w_down,
                   tile: int):
    """``W_d[e] (silu(W_g[e] x[n]) * W_u[e] x[n])`` for every row of the
    grouped layout in use: ``n = row_token[r]`` the row's token (``x``:
    ``[N, D]``, used in the matrices' type; ``row_token``: ``[R]``), ``e
    = tile_expert[r // tile]`` the expert of the row's tile. Each row of
    the result is a slab of 32-bit words (``[R, words, lanes]``,
    :func:`slab_shape`; float32 for float32 matrices, uint32 holding two
    bfloat16 each otherwise), which :func:`combine_held` copies one by
    one.

    No grouped copy of ``x`` exists: the kernel takes the rows of a tile
    in use from ``x`` itself (:func:`row_slabs`), one DMA a row, the next
    tile's while this one's products run; nothing is fetched for a tile
    past ``tiles_used``, and its rows of the result are left as they are
    found. A padding row (``row_token == N``) is not skipped: it is
    pointed at token ``N - 1``, so what the result holds in a padding
    row is unspecified, and :func:`combine_held` never reads it."""
    rows, = row_token.shape
    n, d = x.shape
    f = w_gate.shape[2]
    dtype = w_gate.dtype
    slab = words, lanes = slab_shape(d, dtype)
    word = slab_word(dtype)
    block = width_block(d, f, dtype.itemsize)
    # a block of a flat array in scalar memory is a multiple of 1,024 words:
    # whole tiles of `row_token`, as few as make one
    chunk_tiles = 1024 // math.gcd(tile, 1024)
    chunk = chunk_tiles * tile
    # a padding row names the row past the last: it takes the last one's (the
    # kernel is compiled without bounds checks)
    tokens = jnp.minimum(jnp.pad(row_token, (0, -rows % chunk)), n - 1)

    def in_use(t, tiles_used):
        # a tile past the last one in use names the last one's blocks:
        # nothing is fetched for it and nothing written
        return jnp.minimum(t, jnp.maximum(tiles_used[0] - 1, 0))

    def tokens_block(t, b, tile_expert, tiles_used):
        return in_use(t, tiles_used) // chunk_tiles,

    def next_tokens_block(t, b, tile_expert, tiles_used):
        return in_use(t + 1, tiles_used) // chunk_tiles,

    def slab_block(t, b, tile_expert, tiles_used):
        return in_use(t, tiles_used), 0, 0

    def width(t, b, tiles_used):
        # past the last tile in use: the block already there
        return jnp.where(t < tiles_used[0], b, f // block - 1)

    def in_block(t, b, tile_expert, tiles_used):
        return tile_expert[t], 0, width(t, b, tiles_used)

    def out_block(t, b, tile_expert, tiles_used):
        return tile_expert[t], width(t, b, tiles_used), 0

    return pl.pallas_call(
        functools.partial(_experts_kernel, f // block),
        out_shape=jax.ShapeDtypeStruct((rows, *slab), word),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile, f // block),
            in_specs=[pl.BlockSpec((chunk,), tokens_block,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((chunk,), next_tokens_block,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, d, block), in_block),
                      pl.BlockSpec((1, d, block), in_block),
                      pl.BlockSpec((1, block, d), out_block)],
            out_specs=pl.BlockSpec((tile, *slab), slab_block),
            scratch_shapes=[pltpu.VMEM((2 * tile * words, lanes), word),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((tile, d), dtype)]
            + ([] if block == f else [pltpu.VMEM((tile, d), jnp.float32)])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            disable_bounds_checks=True,
            # two copies of a block of an expert's matrices, of a tile's slabs
            # in and out, the tile, the down-product and what carries it
            vmem_limit_bytes=int(2 * 3 * d * block * dtype.itemsize
                                 + 8 * tile * d * 4 + (8 << 20))),
        interpret=_use_interpreter(),
        name=SCOPE,
    )(tile_expert, tiles_used.reshape(1), tokens, tokens, row_slabs(x, dtype),
      w_gate, w_up, w_down)


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

    def row_copy(row, a):
        return pltpu.make_async_copy(
            y_ref.at[row], buf.at[pl.ds(_times(a, words), words)], sem)

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
        n0 = pl.multiple_of(_times(group, 8), 8)
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
                first = lax.add(_times(lax.add(n0, np.int32(j * tb)), words), s)
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
            o_ref[pl.ds(n0, 8), pl.ds(pl.multiple_of(_times(s, width), width), width)] = (
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
        y_rows = grouped_swiglu(x, row_token, tile_expert, tiles_used, w_gate,
                                w_up, w_down, tile)
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
