"""What each kernel of the ``axk1`` configurations has to do in one device step, from the
configuration's shapes alone: ``kernel_work.py`` for latent attention and for a model whose
first layers do not route. Pure functions of ``(config, rows of a step, tokens of a row)``
returning ``{"flops", "bytes", "calls"}`` for all the layers of the kernel's kind together;
nothing here touches JAX. ``readers/trace_kernel_roofline_from.py`` is told which to call."""

from __future__ import annotations

from benchmarks import kernel_work


def latent_attention(config: dict, rows: int, tokens: int) -> dict:
    """Causal softmax attention between the rebuilt, rotated q, k, v and its output, one
    call a layer. Operations: the two products over the causal half of the square, the
    scores ``qk_nope_head_dim + qk_rope_head_dim`` wide and the values ``v_head_dim``.
    Bytes: ``q`` (both parts), ``k_nope`` and ``v`` per head in at 2 bytes, the rotary key
    once for all heads, ``o`` out in float32."""
    calls = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return {"flops": calls * 2 * rows * (tokens * tokens // 2) * heads * (dn + dr + dv),
            "bytes": calls * rows * tokens * (2 * (heads * (dn + dr + dn + dv) + dr)
                                              + 4 * heads * dv),
            "calls": calls}


def routed_experts(config: dict, rows: int, tokens: int) -> dict:
    """``kernel_work.moe_experts`` over the layers that route: the leading
    ``first_k_dense_replace`` layers have no experts."""
    routed = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return kernel_work.moe_experts(dict(config, num_hidden_layers=routed), rows, tokens)
