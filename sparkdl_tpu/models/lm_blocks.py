"""What the three token models (``models/qwen3_next.py``,
``models/axk1.py``, ``models/ouro.py``) share: the product in the
parameters' storage type, the SwiGLU, the RMS norm, rotate-half rotary,
the share of the experts a tree holds and its counts (the two that
route), the scoring head, and the ``ModelFunction`` over token rows.

Parameters are stored in bfloat16 (norm weights in float32). Matrix
products take bfloat16 and accumulate in float32; the residual stream,
norms and the head's log-softmax are float32. float32 matrices make
every product exact, which the tests use.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.graph.function import ModelFunction

BF16 = jnp.bfloat16
F32 = jnp.float32
#: positions whose logits the head holds at once (x vocabulary x 4 bytes)
_HEAD_BLOCK = 2048


def dot(x, w):
    """Operands in the matrix's storage type (bfloat16; float32 matrices
    make the product exact, which the tests use), float32 result."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=F32,
                   precision=(jax.lax.Precision.HIGHEST if w.dtype == F32
                              else None))


def swiglu(x, w_gate, w_up, w_down):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def rms_norm(x, weight, eps: float, centre: float = 0.0):
    """``x / sqrt(mean(x^2) + eps) * (centre + w)`` in float32: ``centre``
    1 is a zero-centred norm's weight."""
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (centre + weight.astype(F32))


def rotate_half(x, inv_freq):
    """Rotate-half rotary embedding on the first ``2 * len(inv_freq)`` of
    the last axis of ``x`` (``[B, T, H, d]``, position = index on axis 1,
    angle = position x ``inv_freq``, a float64 numpy array); the rest
    passes through untouched."""
    half = len(inv_freq)
    angle = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), F32)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def experts_held(config: Dict[str, Any], width: int) -> tuple:
    """``(first, end)`` of the experts whose matrices the tree holds:
    ``config["experts_held"]``, or all ``width`` the router scores."""
    first, end = config.get("experts_held", (0, width))
    return int(first), int(end)


def held_counts(experts, rows: int, held: tuple):
    """Per row, the assignments each expert of ``held = (first, end)``
    received: ``[B, end - first]``. ``experts``: ``[B * T, k]``."""
    ids = jnp.arange(*held, dtype=jnp.int32)
    return jnp.sum(experts.reshape(rows, -1, 1) == ids, axis=1,
                   dtype=jnp.int32)


def routing_output(routing):
    """The layers' :func:`held_counts` as the ``routing`` output: int32
    ``[B, L, 1 + held]``, column 0 the sum of the rest."""
    counts = jnp.stack(routing, axis=1)  # [B, L, held]
    return jnp.concatenate(
        [jnp.sum(counts, axis=-1, keepdims=True), counts], axis=-1)


def score_head(x, tokens, head):
    """The log-probability of each token after the first, given those
    before it: ``x`` float32 ``[B, T, D]`` (the final norm's output),
    ``tokens`` int32 ``[B, T]``, ``head`` ``[D, vocabulary]`` ->
    float32 ``[B, T - 1]``."""
    with jax.named_scope("Head"):
        x, following = x[:, :-1], tokens[:, 1:]
        # the logits of all of a row's positions at once are T x vocabulary
        # in float32; the head walks the positions in blocks instead
        logprobs = []
        for lo in range(0, x.shape[1], _HEAD_BLOCK):
            logits = dot(x[:, lo:lo + _HEAD_BLOCK], head)
            picked = jnp.take_along_axis(
                logits, following[:, lo:lo + _HEAD_BLOCK, None], axis=-1)[..., 0]
            logprobs.append(picked - jax.nn.logsumexp(logits, axis=-1))
        return jnp.concatenate(logprobs, axis=1)


def scoring_function(forward: Callable, config: Dict[str, Any], params, *,
                     seq_len: int, name: str,
                     outputs: Sequence[str]) -> ModelFunction:
    """A token model's ``forward(params, tokens, config)`` as a
    :class:`ModelFunction` over rows of ``seq_len`` int32 ``tokens``. The
    outputs are the model's to name: ``logprobs`` and, in
    ``models/qwen3_next.py`` and ``models/axk1.py`` with their
    ``routing_stats``, ``routing``; in ``models/ouro.py`` ``exit_pdf``. A
    model with a switch of its own binds it before it hands ``forward``
    over."""
    config = dict(config)

    def apply_fn(params_, inputs):
        return forward(params_, inputs["tokens"].astype(jnp.int32), config)

    return ModelFunction(
        apply_fn, params,
        input_signature={"tokens": ((int(seq_len),), jnp.int32)},
        output_names=list(outputs), name=name)


def shape_tree(tree: dict) -> dict:
    """``{name: (shape, dtype)}`` nested -> a ``jax.ShapeDtypeStruct`` a leaf."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(*leaf), tree,
        is_leaf=lambda node: isinstance(node, tuple))


def draw_tree(shapes: dict, seed: int, special: Callable) -> dict:
    """Seeded stand-ins for trained weights over a :func:`shape_tree`, on
    the default device. ``special(leaf name, key, shape, dtype)`` draws
    the leaves that have a distribution of their own (norm weights, a
    decay's parameters) and returns None for the rest, which are
    matrices: normal at ``1 / sqrt(fan_in)`` (1 for the embedding's
    rows)."""
    key = jax.random.PRNGKey(seed)
    count = [0]

    def draw(path, spec):
        count[0] += 1
        k = jax.random.fold_in(key, count[0])
        leaf = path[-1]
        given = special(leaf, k, spec.shape, spec.dtype)
        if given is not None:
            return given
        # a matrix's rows; the convolution's taps; 1 for the embedding's rows
        fan_in = (1 if leaf == "embed"
                  else spec.shape[-2] if len(spec.shape) > 1 else spec.shape[0])
        return (jax.random.normal(k, spec.shape, F32) / math.sqrt(fan_in)
                ).astype(spec.dtype)

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return draw(path, node)

    return walk(shapes, ())
