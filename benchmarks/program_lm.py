"""The system under test for a language-model configuration: the program's own
builder (``sparkdl_tpu.models.<module>.model_function``) carrying the benchmark's
weights. As ``program.py`` is for the image zoo, this is the one place that knows how
the program names its parameter tree; the reference knows only its own flat names."""

from __future__ import annotations

import importlib

_BLOCKS = {"GatedDeltaNet_0": "mixer", "GatedAttention_0": "mixer", "SparseMoe_0": "moe"}


def to_program_tree(weights: dict) -> dict:
    """The reference's ``{"Layer_3/GatedAttention_0/q_proj": array}`` as the program's
    ``{"layer_3": {"mixer": {"q_proj": array}}}``."""
    tree: dict = {}
    for path, value in weights.items():
        *scopes, leaf = path.split("/")
        node = tree
        for scope in scopes:
            name = _BLOCKS.get(scope) or scope.replace("Layer_", "layer_")
            node = node.setdefault(name, {})
        node[leaf] = value
    return tree


def model_function(config: dict, weights: dict, seq_len: int, routing_stats: bool = False):
    from benchmarks.program import _same_structure
    program = config["program"]
    module = importlib.import_module(f"sparkdl_tpu.models.{program['module']}")
    # the benchmark's file counts under num_experts the experts held here (the guide's
    # rule for a share); the program's own key of that name is the router's width
    config = dict(config, num_experts=config.get("router_width", config["num_experts"]))
    tree = to_program_tree(weights)
    _same_structure(tree, module.param_shapes(config))
    return module.model_function(config, tree, seq_len=seq_len, routing_stats=routing_stats)
