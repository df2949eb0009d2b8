"""The system under test, as the benchmark takes hold of it: the zoo's
ModelFunction for a configuration, carrying the benchmark's weights.

This file is the one place that knows how the program lays out its parameter tree
(flax collections ``params`` and ``batch_stats``, modules named in order of
construction); the reference knows only its own flat names."""

from __future__ import annotations

import numpy as np


def to_program_tree(weights: dict) -> dict:
    """The benchmark's flat ``{"A_0/B_1/kernel": array}`` as the program's nested
    variables: running statistics under ``batch_stats``, the rest under ``params``."""
    tree = {"params": {}, "batch_stats": {}}
    for path, value in weights.items():
        *scopes, leaf = path.split("/")
        node = tree["batch_stats" if leaf in ("mean", "var") else "params"]
        for scope in scopes:
            node = node.setdefault(scope, {})
        node[leaf] = value
    return tree


def _same_structure(ours, theirs, at=""):
    if isinstance(theirs, dict) != isinstance(ours, dict):
        raise ValueError(f"weights differ from the program's tree at {at or '/'}")
    if isinstance(theirs, dict):
        if set(ours) != set(theirs):
            raise ValueError(
                f"weights differ from the program's tree at {at or '/'}: "
                f"{sorted(set(ours) ^ set(theirs))[:6]}")
        for k in theirs:
            _same_structure(ours[k], theirs[k], f"{at}/{k}")
    elif tuple(np.shape(ours)) != tuple(np.shape(theirs)):
        raise ValueError(f"{at}: shape {np.shape(ours)}, the program has {np.shape(theirs)}")


def model_function(config: dict, weights: dict):
    """``zoo.getModelFunction`` for the configuration, its seeded-random parameters
    replaced by the benchmark's (same tree, same shapes, checked)."""
    from sparkdl_tpu.models.zoo import getModelFunction
    program = config["program"]
    mf = getModelFunction(program["zoo_name"], featurize=program["featurize"])
    tree = to_program_tree(weights)
    _same_structure(tree, mf.params)
    mf.params = tree
    return mf
