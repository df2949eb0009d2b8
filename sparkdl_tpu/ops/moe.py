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
hidden activations never leave VMEM.

Products take bfloat16 and accumulate in float32; router
probabilities and the weighted sum are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernel's name, and the scope the layout round it carries
SCOPE = "moe_experts"


def _use_interpreter() -> bool:
    """Off the TPU the kernel runs in Pallas's interpreter."""
    return jax.default_backend() != "tpu"


def route(logits, k: int):
    """``(experts [N, k] int32, weights [N, k] float32)``: the ``k``
    most probable experts of a float32 softmax over all of them, their
    probabilities renormalised to sum to 1."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = lax.top_k(p, k)
    return top_i.astype(jnp.int32), top_p / jnp.sum(top_p, -1, keepdims=True)


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


def _experts_kernel(tile_expert_ref, tiles_used_ref, x_ref, wg_ref, wu_ref,
                    wd_ref, o_ref):
    del tile_expert_ref  # read by the index maps

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
        o_ref[...] = dot(hidden, wd_ref[0]).astype(o_ref.dtype)


def grouped_swiglu(x_rows, tile_expert, tiles_used, w_gate, w_up, w_down,
                   tile: int):
    """``W_d[e] (silu(W_g[e] x) * W_u[e] x)`` for every row of the
    grouped buffer ``x_rows`` (``[R, D]``), ``e`` the expert of the
    row's tile. Rows of tiles past ``tiles_used`` are left as they
    are found."""
    rows, d = x_rows.shape
    held, _, f = w_gate.shape

    def row_block(t, tile_expert, tiles_used):
        # a tile past the last one in use names the last one's block:
        # nothing is fetched for it and nothing written
        return jnp.minimum(t, jnp.maximum(tiles_used[0] - 1, 0)), 0

    def expert_block(t, tile_expert, tiles_used):
        return tile_expert[t], 0, 0

    weights = 3 * d * f * w_gate.dtype.itemsize
    return pl.pallas_call(
        _experts_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, d), x_rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, d), row_block),
                      pl.BlockSpec((1, d, f), expert_block),
                      pl.BlockSpec((1, d, f), expert_block),
                      pl.BlockSpec((1, f, d), expert_block)],
            out_specs=pl.BlockSpec((tile, d), row_block)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two copies of an expert's matrices and of the row tiles
            vmem_limit_bytes=int(2 * weights + 8 * tile * d * 4 + (8 << 20))),
        interpret=_use_interpreter(),
        name=SCOPE,
    )(tile_expert, tiles_used.reshape(1), x_rows, w_gate, w_up, w_down)


def held_experts_ffn(x, experts, weights, w_gate, w_up, w_down, first: int,
                     tile: int = 128):
    """The held experts' part of the routed feed-forward.

    ``x``: ``[N, D]``; ``experts``, ``weights``: ``[N, k]`` from
    :func:`route`; ``w_gate``, ``w_up``: ``[held, D, F]``, ``w_down``:
    ``[held, F, D]``, the matrices of experts ``first .. first +
    held``. Returns ``(y [N, D] float32, counts [held] int32)``:
    ``y[n] = sum over j with experts[n, j] held of weights[n, j] *
    expert(x[n])``, and the assignments each held expert received."""
    with jax.named_scope(SCOPE):
        d = x.shape[1]
        held = w_gate.shape[0]
        (row_token, dest, is_held, tile_expert, tiles_used,
         counts) = grouped_layout(experts, first, held, tile)
        x = x.astype(w_gate.dtype)
        x_rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[row_token]
        y_rows = grouped_swiglu(x_rows, tile_expert, tiles_used, w_gate, w_up,
                                w_down, tile)
        # choice by choice ([k, N, D]): a [N, k, D] gather would pad k to
        # the tile's 16 rows and be re-laid out before the sum
        picked = y_rows[jnp.where(is_held, dest, 0).T].astype(jnp.float32)
        y = jnp.sum(jnp.where(is_held.T[..., None],
                              picked * weights.T[..., None], 0.0), axis=0)
        return y, counts
