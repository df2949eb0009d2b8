"""What the attention kernel of a looped configuration (``ouro_2p6b``) has to do in one
device step, from the configuration's shapes alone: ``kernel_work.py`` for a model whose
layers run ``total_ut_steps`` times. ``kernel_work.attention`` counts the layers by
``full_attention_interval`` and would count each once; here a call is one application of
a layer. A pure function of ``(config, rows of a step, tokens of a row)`` returning
``{"flops", "bytes", "calls"}`` for all the calls together; nothing here touches JAX.
``readers/trace_kernel_roofline_from.py`` is told which to call."""

from __future__ import annotations


def attention(config: dict, rows: int, tokens: int) -> dict:
    """Causal softmax attention between rotated q, k, v and its output, one call a layer and
    pass. Operations: the two products over the causal half of the square, ``head_dim`` wide,
    for every query head. Bytes: q, k, v in at 2 bytes (each key head once), o out in float32."""
    calls = config["num_hidden_layers"] * config["total_ut_steps"]
    heads, kv_heads, d = (config["num_attention_heads"], config["num_key_value_heads"],
                          config["head_dim"])
    return {"flops": calls * 4 * rows * (tokens * tokens // 2) * d * heads,
            "bytes": calls * rows * tokens * d * (2 * (heads + 2 * kv_heads) + 4 * heads),
            "calls": calls}
