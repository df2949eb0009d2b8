"""Weights and reference answers for a language-model configuration.

``model.make_weights`` draws every leaf in float32 from one flat draw (14.7 GB for
``qwen3next_80b_a3b_ep4``) and ``model.reference_outputs`` feeds uint8 images, so a
configuration whose parameters are stored in bfloat16 and whose rows are tokens brings
this file instead. The architecture is still walked once by ``model._survey`` (the
reference's own ``forward`` over ``Net()``), so parameters, shapes and distributions
have one source. Each leaf is drawn on the device from ``--seed`` and its place in the
walk: matrices (the ``normal`` leaves) are stored in bfloat16, as the configuration
states; norm weights, ``A_log`` and ``dt_bias`` (the ``uniform`` leaves) stay float32.
Nothing here imports the program."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import model
from benchmarks.reference.nn import Net


@functools.lru_cache(maxsize=None)
def _draw(shape: tuple, kind: str, args: tuple):
    if kind == "normal":
        return jax.jit(lambda key: (jax.random.normal(key, shape, jnp.float32) * args[0]
                                    ).astype(jnp.bfloat16))
    lo, hi = args
    return jax.jit(lambda key: jax.random.uniform(key, shape, jnp.float32, lo, hi))


def make_weights(config: dict, seed: int) -> dict:
    """{path: array} on the default device, the same for the same seed."""
    key = jax.random.wrap_key_data(jnp.asarray(model.seed_key(seed)))
    weights = {}
    for index, (path, (shape, kind, *args)) in enumerate(model._survey(config).specs.items()):
        weights[path] = _draw(tuple(shape), kind, tuple(args))(jax.random.fold_in(key, index))
    return weights


def token_rows(seed: int, rows: int, length: int, vocab: int, exponent: float) -> np.ndarray:
    """``rows`` rows of ``length`` int32 token ids from the seed, drawn with a Zipf law
    over the ``vocab`` ids (rank = id + 1: id 0 is the commonest)."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    weight = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(weight / weight.sum())
    ids = np.searchsorted(cdf, rng.random((rows, length)), side="right")
    return np.minimum(ids, vocab - 1).astype(np.int32)


@functools.lru_cache(maxsize=4)
def _reference_fn(config_json: str, quant, names: tuple, broken: tuple):
    config = json.loads(config_json)
    forward = model._forward(config)

    @jax.jit
    def apply(params, tokens):
        out = forward(Net(params=params, quant=quant), tokens, config, **dict(broken))
        return tuple(out[name] for name in names)

    return apply


def reference_outputs(config: dict, weights: dict, tokens: np.ndarray, quant=None,
                      block: int = 2, outputs=None, routing: bool = False, **broken):
    """The reference's head (``config["head"]``) over ``tokens``, ``block`` rows at a
    time so that it fits the chip. With ``quant``, the control; with keywords of the
    reference's own ``forward`` (``use_decay=False``, ``passes=3``), a wrong program.
    Given ``outputs`` (names of the reference's other outputs, as the configuration's
    ``program`` block names them), ``(head, *those)`` in that order; ``routing=True``, the
    form the program's own tests call, is ``outputs=["routing"]``."""
    names = (config["head"], *(["routing"] if routing else outputs or ()))
    apply = _reference_fn(json.dumps(config, sort_keys=True), quant, names,
                          tuple(sorted(broken.items())))
    outs = [[] for _ in names]
    for lo in range(0, len(tokens), block):
        chunk = tokens[lo:lo + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, axis=0)])
        for kept, answer in zip(outs, apply(weights, chunk)):
            kept.append(np.asarray(answer)[:block - pad])
    found = tuple(np.concatenate(kept) for kept in outs)
    return found if outputs is not None or routing else found[0]
