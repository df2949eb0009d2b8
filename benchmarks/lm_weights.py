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
def _reference_fn(config_json: str, quant, broken):
    config = json.loads(config_json)
    forward = model._forward(config)

    @jax.jit
    def apply(params, tokens):
        kwargs = {"use_decay": False} if broken == "no_decay" else {}
        out = forward(Net(params=params, quant=quant), tokens, config, **kwargs)
        return out[config["head"]], out["routing"]

    return apply


def reference_outputs(config: dict, weights: dict, tokens: np.ndarray, quant=None,
                      broken=None, block: int = 2, routing: bool = False):
    """The reference (with ``quant``, the control; with ``broken``, a wrong program for
    the tests) over ``tokens``, ``block`` rows at a time so that it fits the chip. With
    ``routing``, ``(answers, [rows, layers, held] counts of the reference's own routing)``."""
    apply = _reference_fn(json.dumps(config, sort_keys=True), quant, broken)
    outs, counts = [], []
    for lo in range(0, len(tokens), block):
        chunk = tokens[lo:lo + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, axis=0)])
        answers, routed = apply(weights, chunk)
        outs.append(np.asarray(answers)[:block - pad])
        counts.append(np.asarray(routed)[:block - pad])
    return (np.concatenate(outs), np.concatenate(counts)) if routing else np.concatenate(outs)
