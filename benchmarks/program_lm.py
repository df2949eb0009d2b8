"""The system under test for a language-model configuration: the program's own
builder (``sparkdl_tpu.models.<module>.model_function``) carrying the benchmark's
weights, and what the program's outputs beside its log-probabilities say, into the
program's registry. As ``program.py`` is for the image zoo, this is the one place that
knows how the program names its parameter tree; the reference knows only its own flat
names.

What differs from one configuration to the next is data, in the configuration's
``program`` block:

- ``module``: the program, ``sparkdl_tpu.models.<module>``;
- ``blocks``: the reference's scopes under other names in the program
  (``{"GatedAttention_0": "mixer"}``; ``Layer_<i>`` is ``layer_<i>`` everywhere);
- ``keys``: the program's configuration keys that take another key of the file
  (``{"num_experts": "router_width"}``: the file counts the experts held);
- ``options``: keywords of the program's ``model_function`` that the benchmark's runs turn on
  (``{"routing_stats": true}``);
- ``outputs``: each output of the program besides ``logprobs`` that a run keeps, and
  the function that records it, as ``<module>.<function>`` under ``benchmarks/``
  (``record_routing`` and ``record_exit`` below), called as
  ``(summed over the window's rows, rows, tokens a row, configuration)`` and returning
  the counters it moved.

The builder reads nothing but that block, with one exception: the program's own tests
write a configuration by its ``module`` alone, and a block that names nothing else takes
``blocks`` and ``keys`` from the one file that ``_NAMING`` gives its module. Every file
under ``configs/`` names more than its module, so no cell reaches it, and a file added there
changes what no other configuration builds."""

from __future__ import annotations

import importlib
import json
import os

import numpy as np

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs")
# module -> the configuration file whose naming a block of that module alone takes
_NAMING = {"qwen3_next": "qwen3next_80b_a3b_ep4.json", "axk1": "axk1_ep16.json"}


def _naming(block: dict) -> dict:
    """``blocks`` and ``keys`` of ``block``; for a block of its module alone, those of the
    file that ``_NAMING`` gives it."""
    if set(block) == {"module"} and block["module"] in _NAMING:
        with open(os.path.join(CONFIGS, _NAMING[block["module"]])) as f:
            block = json.load(f)["program"]
    return {"blocks": block.get("blocks", {}), "keys": block.get("keys", {})}


def to_program_tree(weights: dict, blocks: dict) -> dict:
    """The reference's ``{"Layer_3/GatedAttention_0/q_proj": array}`` as the program's
    ``{"layer_3": {"mixer": {"q_proj": array}}}``."""
    tree: dict = {}
    for path, value in weights.items():
        *scopes, leaf = path.split("/")
        node = tree
        for scope in scopes:
            node = node.setdefault(blocks.get(scope) or scope.replace("Layer_", "layer_"), {})
        node[leaf] = value
    return tree


def model_function(config: dict, weights: dict, seq_len: int, **options):
    """The program built as the configuration's ``program`` block says, carrying
    ``weights``; ``options`` are keywords of the program's own ``model_function``."""
    from benchmarks.program import _same_structure
    module = importlib.import_module(f"sparkdl_tpu.models.{config['program']['module']}")
    naming = _naming(config["program"])
    config = dict(config, **{key: config[file_key] for key, file_key in naming["keys"].items()})
    tree = to_program_tree(weights, naming["blocks"])
    _same_structure(tree, module.param_shapes(config))
    return module.model_function(config, tree, seq_len=seq_len, **options)


def recorder(path: str):
    """The function ``<module>.<function>`` under ``benchmarks/``."""
    module, function = path.rsplit(".", 1)
    return getattr(importlib.import_module(f"benchmarks.{module}"), function)


def _moved(record, counters: tuple, gauges: tuple) -> dict:
    from sparkdl_tpu.obs.registry import default_registry
    registry = default_registry()
    before = registry.snapshot()
    record()
    after = registry.snapshot()
    return {**{k: after[k] - before.get(k, 0.0) for k in counters}, **{k: after[k] for k in gauges}}


def record_routing(total: np.ndarray, rows: int, tokens: int, config: dict) -> dict:
    """The ``routing`` output (a row: per layer that routes, all but the leading
    ``first_k_dense_replace``, the assignments held here, then each held expert's) summed
    over the window's rows, into ``ops/moe.py``'s counters; and the most one held expert of
    one layer received over the mean."""
    from sparkdl_tpu.ops.moe import record_routing as record
    layers = config["num_hidden_layers"] - config.get("first_k_dense_replace", 0)
    held = config["experts_held"][1] - config["experts_held"][0]
    counts = np.asarray(total).reshape(layers, 1 + held).astype(np.int64)
    assignments = rows * tokens * config["num_experts_per_tok"] * layers
    moved = _moved(lambda: record(counts, assignments=assignments),
                   ("moe.assignments", "moe.assignments_held"), ("moe.expert_load_max",))
    load_mean = moved["moe.assignments_held"] / (layers * held)
    load_max = moved["moe.expert_load_max"]
    moved["moe.expert_load_max_over_mean"] = load_max / load_mean if load_mean else None
    return moved


def record_exit(total: np.ndarray, rows: int, tokens: int, config: dict) -> dict:
    """The ``exit_pdf`` output (a row: the exit distribution over the loop's passes)
    summed over the window's rows, into the loop's counters (``record_exit`` of the
    configuration's module)."""
    module = importlib.import_module(f"sparkdl_tpu.models.{config['program']['module']}")
    record = module.record_exit
    return _moved(lambda: record(np.asarray(total, np.float64), rows),
                  ("loop.rows",), ("loop.exit_step_mean",))
