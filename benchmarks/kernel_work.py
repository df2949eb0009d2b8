"""What each kernel of a language-model configuration has to do in one device step,
from the configuration's shapes alone: floating-point operations (a multiply-add is two)
and bytes that must cross the chip's memory, whatever implements the kernel. A roofline
share is the larger of operations over the peak rate and bytes over the peak bandwidth,
over the kernel's device time (``readers/trace_kernel_roofline.py``, whose metric file
names the function here as ``kernel_work.<function>``; a later configuration's kernels
may be counted in a file of their own beside this one). Pure functions; nothing here
touches JAX.

Each function takes the configuration, the rows of a device step and the tokens of a row,
and returns ``{"flops", "bytes", "calls"}`` for one step: all the layers of the kernel's
kind together, ``calls`` of them."""

from __future__ import annotations


def _layers(config: dict, full: bool) -> int:
    interval = config.get("full_attention_interval", 1)
    kinds = [(i + 1) % interval == 0 for i in range(config["num_hidden_layers"])]
    return sum(kinds) if full else len(kinds) - sum(kinds)


def gdn_scan(config: dict, rows: int, tokens: int) -> dict:
    """The gated delta rule between its normalised inputs and its output. Operations: per
    position and value head the rule's three ``dk x dv`` products (read ``S^T k``, write
    ``k d^T``, read ``S^T q``). Bytes: q, k, v in and o out in float32, the two gates."""
    calls = _layers(config, full=False)
    heads = config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    positions = rows * tokens * heads
    return {"flops": calls * 3 * 2 * positions * dk * dv,
            "bytes": calls * 4 * positions * (2 * dk + 2 * dv + 2), "calls": calls}


def moe_experts(config: dict, rows: int, tokens: int) -> dict:
    """The routed experts held here, from the routed tokens to their weighted sum, in the
    layers that route (all but the leading ``first_k_dense_replace``). Operations: the
    expected assignments on held experts (``num_experts_per_tok * held / router_width`` a
    token) times the expert's three matrices. Bytes: every held expert's matrices once in
    their storage type, each assignment's row in and out at 2 bytes."""
    calls = config["num_hidden_layers"] - config.get("first_k_dense_replace", 0)
    first, end = config["experts_held"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    assignments = rows * tokens * config["num_experts_per_tok"] * (end - first) / config["router_width"]
    return {"flops": int(calls * 2 * assignments * 3 * d * f),
            "bytes": int(calls * (2 * (end - first) * 3 * d * f + 2 * 2 * assignments * d)),
            "calls": calls}


def attention(config: dict, rows: int, tokens: int) -> dict:
    """Causal softmax attention between rotated q, k, v and its output, one call a full
    attention layer (every ``full_attention_interval``-th; every layer where it is absent)
    and pass of the loop (``total_ut_steps``, where present). Operations: the two products
    over the causal half of the square. Bytes: q, k, v in and o out at 2 bytes, the
    configuration's bfloat16 (each key head once)."""
    calls = _layers(config, full=True) * config.get("total_ut_steps", 1)
    heads, kv_heads, d = (config["num_attention_heads"], config["num_key_value_heads"],
                          config["head_dim"])
    return {"flops": calls * 4 * rows * (tokens * tokens // 2) * d * heads,
            "bytes": calls * rows * tokens * d * 2 * (heads + 2 * kv_heads + heads),
            "calls": calls}


def latent_attention(config: dict, rows: int, tokens: int) -> dict:
    """Causal softmax attention between the rebuilt, rotated q, k, v and its output, one
    call a layer. Operations: the two products over the causal half of the square, the
    scores ``qk_nope_head_dim + qk_rope_head_dim`` wide and the values ``v_head_dim``.
    Bytes in at 2 bytes: ``q`` (both parts) per head, the one ``kv`` array that holds
    ``k_nope`` and ``v`` per head, the rotary key once for all heads; ``o`` out at 2 bytes
    too."""
    calls = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return {"flops": calls * 2 * rows * (tokens * tokens // 2) * heads * (dn + dr + dv),
            "bytes": calls * rows * tokens * 2 * (heads * (dn + dr) + heads * (dn + dv) + dr
                                                  + heads * dv),
            "calls": calls}
