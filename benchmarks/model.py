"""From a configuration file to its weights, its plain reference and its FLOPs.

The weights are the benchmark's, made on the device in one jitted call from
``--seed``; the program and the reference are each handed them. Nothing here
imports the program."""

from __future__ import annotations

import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import ROOT
from benchmarks.reference.nn import Net


def load_config(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _forward(config: dict):
    return importlib.import_module(f"benchmarks.reference.{config['reference']}").forward


def _zeros_row(config: dict):
    return jnp.zeros((1,) + tuple(config["input_shape"]), jnp.uint8)


def seed_key(seed: int):
    """A threefry key from any whole number up to 2**64: both halves are used, so
    seeds past 2**31 neither fail nor collide."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside 0..2**64")
    return np.asarray([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)


def _survey(config: dict) -> Net:
    """The architecture walked once on shapes alone: ``specs`` and ``flops``."""
    net = Net()
    jax.eval_shape(lambda: _forward(config)(net, _zeros_row(config), config))
    return net


def make_weights(config: dict, seed: int) -> dict:
    """{path: float32 array}, on the default device, in one jitted call: one normal
    and one uniform draw, cut into the leaves. The key is an argument, so every
    seed runs the same compiled program."""
    specs = _survey(config).specs
    sizes = {"normal": 0, "uniform": 0}
    for shape, kind, *_ in specs.values():
        sizes[kind] += int(np.prod(shape))

    @jax.jit
    def init(key_data):
        k_normal, k_uniform = jax.random.split(jax.random.wrap_key_data(key_data))
        draws = {"normal": jax.random.normal(k_normal, (sizes["normal"],), jnp.float32),
                 "uniform": jax.random.uniform(k_uniform, (sizes["uniform"],), jnp.float32)}
        at = {"normal": 0, "uniform": 0}
        out = {}
        for path, (shape, kind, *args) in specs.items():
            n = int(np.prod(shape))
            flat = draws[kind][at[kind]:at[kind] + n].reshape(shape)
            at[kind] += n
            out[path] = flat * args[0] if kind == "normal" else args[0] + flat * (args[1] - args[0])
        return out

    return init(seed_key(seed))


def flops_per_row(config: dict) -> int:
    """Multiply-adds (counted as two) of the convolutions and dense layers that one
    row needs, from the configuration's shapes. The dense head is counted only
    where the configuration serves it."""
    flops = _survey(config).flops
    if config["head"] == "features":
        flops -= 2 * config["feature_dim"] * config["num_classes"]
    return int(flops)


@functools.lru_cache(maxsize=4)
def _reference_fn(config_json: str, quant):
    config = json.loads(config_json)
    forward = _forward(config)
    out_name = config["head"]

    @jax.jit
    def apply(params, images):
        return forward(Net(params=params, quant=quant), images, config)[out_name]

    return apply


def reference_outputs(config: dict, weights: dict, images: np.ndarray,
                      quant=None, block: int = 32) -> np.ndarray:
    """The reference (or, with ``quant``, the control) over ``images``, in blocks of
    ``block`` rows so that it fits beside nothing else on the chip."""
    apply = _reference_fn(json.dumps(config, sort_keys=True), quant)
    outs = []
    for lo in range(0, len(images), block):
        chunk = images[lo:lo + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, axis=0)])
        outs.append(np.asarray(apply(weights, chunk))[:block - pad])
    return np.concatenate(outs)
