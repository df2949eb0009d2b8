"""Qwen3-Next as a scoring function over token rows.

The first model here that is not a CNN, and not a member of the image
zoo (``models/zoo.py``): :func:`model_function` builds a
:class:`~sparkdl_tpu.graph.function.ModelFunction` from a configuration
dict (the keys of the model's published ``config.json``) and a
parameter tree, with input ``tokens`` (int32 ``[T]`` a row) and output
``logprobs`` (float32 ``[T - 1]``: the log-probability the model gives
each token after the first, given those before it). It goes through
``TensorTransformer`` like any other ``ModelFunction``::

    mf = qwen3_next.model_function(config, params)
    TensorTransformer(modelFunction=mf, inputMapping={"tokens": "tokens"},
                      outputMapping={"logprobs": "logprobs"}, batchSize=2)

Three kinds of block share one program (``hf`` below is the family's
published modelling code, ``modeling_qwen3_next.py``):

* **Gated delta-rule linear attention** (``GatedDeltaNet_<i>``; layer
  ``i`` unless ``(i + 1) % full_attention_interval == 0``): one
  projection to q, k, v, z and one to the gates b, a; a causal
  depthwise convolution of width ``linear_conv_kernel_dim`` and SiLU
  over q, k, v; q and k L2-normalised at their own heads, in the
  convolution's one array, which the rule itself
  (``ops/gated_delta.py``) reads in place; a gated RMS norm
  (``w * o / rms(o) * silu(z)``) and the out-projection.
* **Gated softmax attention** (``GatedAttention_<i>``): ``q_proj`` gives
  query and gate per head, query and key go through a per-head RMS norm,
  rotary embedding turns the first ``partial_rotary_factor`` of each
  head, ``ops/attention.py`` does the causal softmax, and the result is
  multiplied by ``sigmoid(gate)`` before ``o_proj``.
* **Sparse expert block** (``SparseMoe_<i>``, every layer): a router
  over all ``num_experts``, the ``num_experts_per_tok`` largest
  renormalised, and ``ops/moe.py`` over the experts this chip holds
  (``config["experts_held"] = [first, end)``; the expert arrays hold
  ``end - first``), plus a shared expert behind a sigmoid gate, added
  once.

Parameters are stored in bfloat16 (norm weights, ``A_log``, ``dt_bias``
in float32). Matrix products take bfloat16 and accumulate in float32;
the residual stream, norms, router probabilities, the delta rule's
gates and state, softmaxes and the head's log-softmax are float32.

Departures from ``hf``, none of which changes the mathematics: the
in-projection's 12,288 columns are laid out ``[q | k | v | z]`` and the
gates' 64 ``[b | a]`` (``hf`` interleaves them by key head); L2
normalisation is ``x * rsqrt(sum(x^2) + 1e-6)``; the multi-token
prediction module is left out (a drafting head, not part of scoring).

With ``routing_stats=True`` the function has a second output,
``routing`` (int32 ``[layers, 1 + held]`` a row: per layer, the
assignments of the row's tokens that fell on experts held here, then
the count each held expert received); :func:`record_routing` sums a
column of it into the registry's ``moe.*`` counters. Without it
nothing is counted and nothing comes back.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.models import lm_blocks
from sparkdl_tpu.models.lm_blocks import BF16 as _BF16, F32 as _F32, dot as _dot
from sparkdl_tpu.ops import attention as attention_op
from sparkdl_tpu.ops import gated_delta, moe
from sparkdl_tpu.ops.moe import record_routing  # noqa: F401  (its home since PR 32)


def is_full_attention(config: Dict[str, Any], layer: int) -> bool:
    return (layer + 1) % int(config["full_attention_interval"]) == 0


def experts_held(config: Dict[str, Any]) -> tuple:
    """``(first, end)`` of the experts whose matrices the tree holds:
    ``config["experts_held"]``, or all of them."""
    return lm_blocks.experts_held(config, config["num_experts"])


# -- the blocks ---------------------------------------------------------------

def rms_norm(x, weight, eps: float):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)`` in float32: the family's
    zero-centred norm."""
    return lm_blocks.rms_norm(x, weight, eps, centre=1.0)


def partial_rotary(x, theta: float, rotary_dim: int):
    """Rotate-half rotary embedding on the first ``rotary_dim`` of the
    last axis of ``x`` (``[B, T, H, d]``, position = index on axis 1);
    the rest passes through untouched."""
    inv_freq = 1.0 / (theta ** (np.arange(rotary_dim // 2, dtype=np.float64) * 2
                                / rotary_dim))
    return lm_blocks.rotate_half(x, inv_freq)


def gated_attention(p, x, config):
    b, t, _ = x.shape
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    eps = config["rms_norm_eps"]
    qg = _dot(x, p["q_proj"]).reshape(b, t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _dot(x, p["k_proj"]).reshape(b, t, kv_heads, d)
    v = _dot(x, p["v_proj"]).reshape(b, t, kv_heads, d)
    rotary_dim = int(d * config["partial_rotary_factor"])
    q = partial_rotary(rms_norm(q, p["q_norm"], eps), config["rope_theta"],
                       rotary_dim)
    k = partial_rotary(rms_norm(k, p["k_norm"], eps), config["rope_theta"],
                       rotary_dim)
    o = attention_op.causal_attention(q, k, v, scale=1.0 / math.sqrt(d),
                                      dtype=p["q_proj"].dtype)
    o = o * jax.nn.sigmoid(gate)
    return _dot(o.reshape(b, t, heads * d), p["o_proj"])


def causal_depthwise_conv(x, kernel):
    """``y[t] = sum_j kernel[j] * x[t - (K - 1) + j]`` per channel, zeros
    before the row's start. ``x``: ``[B, H, T, d]`` (channel = head and
    position in it), ``kernel``: ``[K, H, d]``."""
    width = kernel.shape[0]
    t = x.shape[2]
    padded = jnp.pad(x, ((0, 0), (0, 0), (width - 1, 0), (0, 0)))
    kernel = kernel.astype(_F32)
    return sum(padded[:, :, j:j + t] * kernel[j][None, :, None, :]
               for j in range(width))


def _l2_normalise(x, heads: int):
    """``x`` (``[B, H, T, d]``) with its first ``heads`` heads
    L2-normalised, ``x * rsqrt(sum(x^2) + 1e-6)``, and the rest times 1.
    The sums are taken over every head, with no slice, so that XLA takes
    them in the fusion that writes ``x`` and reads ``x`` once more."""
    head = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1], 1, 1), 1)
    return x * jnp.where(head < heads, jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6), 1.0)


def gated_delta_net(p, x, config):
    """Heads first throughout (``[B, H, T, d]``): the in-projection
    writes that layout, the convolution and the rule run along ``T`` in
    it, the out-projection reads it; no sequence is ever transposed.
    The rule reads q, k and v where the convolution's fusion wrote
    them, one array of ``2 hk + hv`` heads: the key heads unrepeated,
    each range in place (``gated_delta.Heads``)."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    if dk != dv:
        raise ValueError("the heads-first layout needs key and value heads "
                         f"of one size, got {dk} and {dv}")
    w_in = p["in_proj_qkvz"]
    dtype = w_in.dtype
    precision = jax.lax.Precision.HIGHEST if dtype == _F32 else None
    # columns [q | k | v | z], each head after head: 2 hk + 2 hv heads of
    # dk. [q | k | v] and z are two products, so that the convolution
    # reads a whole result (a slice of one product's result is a copy)
    w_in = w_in.reshape(w_in.shape[0], -1, dk)
    qkv, z = (jnp.einsum("btd,dhk->bhtk", x.astype(dtype), w,
                         preferred_element_type=_F32, precision=precision)
              for w in (w_in[:, :2 * hk + hv], w_in[:, 2 * hk + hv:]))
    ba = jnp.swapaxes(_dot(x, p["in_proj_ba"]), 1, 2)  # [B, 2 hv, T]
    qkv = jax.nn.silu(causal_depthwise_conv(
        qkv, p["conv"].reshape(-1, 2 * hk + hv, dk)))
    # q and k L2-normalised, q scaled, v as it is: one array
    qkv = _l2_normalise(qkv, 2 * hk)
    head = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * hk + hv, 1, 1), 1)
    qkv = jnp.where(head < hk, qkv * (1.0 / math.sqrt(dk)), qkv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"].astype(_F32))[:, None] * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(_F32)[:, None])
    o = gated_delta.gated_delta_rule(
        gated_delta.Heads(qkv, 0, hk), gated_delta.Heads(qkv, hk, hk),
        gated_delta.Heads(qkv, 2 * hk, hv), g, beta, dtype=dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + config["rms_norm_eps"])
    o = o * p["norm"].astype(_F32) * jax.nn.silu(z)
    w_out = p["out_proj"]
    return jnp.einsum("bhtv,hvd->btd", o.astype(dtype),
                      w_out.reshape(hv, dv, w_out.shape[-1]),
                      preferred_element_type=_F32, precision=precision)


def sparse_moe(p, x, config):
    """``(y, experts)``: the block's output for ``x`` (``[B, T, D]``) and
    the experts each token chose (``[B * T, k]``)."""
    b, t, d = x.shape
    first, _ = experts_held(config)
    flat = x.reshape(b * t, d)
    experts, weights = moe.route(_dot(flat, p["router"]),
                                 config["num_experts_per_tok"])
    routed, _ = moe.held_experts_ffn(
        flat, experts, weights, p["experts_gate"], p["experts_up"],
        p["experts_down"], first=first)
    shared = lm_blocks.swiglu(flat, p["shared_gate"], p["shared_up"],
                              p["shared_down"])
    shared = shared * jax.nn.sigmoid(_dot(flat, p["shared_router"][:, None]))
    return (routed + shared).reshape(b, t, d), experts


def final_hidden(params, tokens, config, routing_stats: bool = False):
    """The residual stream after the last layer and the final norm
    (float32 ``[B, T, D]``), and per layer the held experts' counts
    (``[B, held]`` each; empty without ``routing_stats``)."""
    eps = config["rms_norm_eps"]
    x = params["embed"][tokens].astype(_F32)
    routing = []
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        h = rms_norm(x, p["norm1"], eps)
        if is_full_attention(config, i):
            with jax.named_scope(f"GatedAttention_{i}"):
                x = x + gated_attention(p["mixer"], h, config)
        else:
            with jax.named_scope(f"GatedDeltaNet_{i}"):
                x = x + gated_delta_net(p["mixer"], h, config)
        with jax.named_scope(f"SparseMoe_{i}"):
            y, experts = sparse_moe(p["moe"], rms_norm(x, p["norm2"], eps),
                                    config)
            if routing_stats:
                routing.append(lm_blocks.held_counts(
                    experts, x.shape[0], experts_held(config)))
        x = x + y
    return rms_norm(x, params["final_norm"], eps), routing


def forward(params, tokens, config, routing_stats: bool = False):
    """``tokens`` int32 ``[B, T]`` -> ``{"logprobs": float32 [B, T - 1]}``
    and, with ``routing_stats``, ``"routing"`` int32 ``[B, L, 1 + held]``."""
    x, routing = final_hidden(params, tokens, config, routing_stats)
    out = {"logprobs": lm_blocks.score_head(x, tokens, params["head"])}
    if routing_stats:
        out["routing"] = lm_blocks.routing_output(routing)
    return out


# -- the ModelFunction --------------------------------------------------------

def model_function(config: Dict[str, Any], params, *, seq_len: int,
                   routing_stats: bool = False) -> ModelFunction:
    """The scoring function over rows of ``seq_len`` tokens. ``config``
    holds the published keys (and ``experts_held`` where the tree holds
    a share of the experts); ``params`` is the tree :func:`param_shapes`
    describes."""
    return lm_blocks.scoring_function(
        functools.partial(forward, routing_stats=routing_stats), config, params,
        seq_len=seq_len, name="Qwen3Next",
        outputs=["logprobs"] + (["routing"] if routing_stats else []))


def param_shapes(config: Dict[str, Any]) -> dict:
    """The parameter tree, a ``jax.ShapeDtypeStruct`` for each leaf."""
    d, vocab = config["hidden_size"], config["vocab_size"]
    first, end = experts_held(config)
    held, f = end - first, config["moe_intermediate_size"]
    fs = config["shared_expert_intermediate_size"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    conv_dim = 2 * hk * dk + hv * dv
    moe_block = {
        "router": ((d, config["num_experts"]), _BF16),
        "experts_gate": ((held, d, f), _BF16), "experts_up": ((held, d, f), _BF16),
        "experts_down": ((held, f, d), _BF16),
        "shared_gate": ((d, fs), _BF16), "shared_up": ((d, fs), _BF16),
        "shared_down": ((fs, d), _BF16), "shared_router": ((d,), _BF16)}
    delta = {
        "in_proj_qkvz": ((d, conv_dim + hv * dv), _BF16),
        "in_proj_ba": ((d, 2 * hv), _BF16),
        "conv": ((config["linear_conv_kernel_dim"], conv_dim), _BF16),
        "A_log": ((hv,), _F32), "dt_bias": ((hv,), _F32), "norm": ((dv,), _F32),
        "out_proj": ((hv * dv, d), _BF16)}
    full = {
        "q_proj": ((d, 2 * heads * hd), _BF16), "k_proj": ((d, kv_heads * hd), _BF16),
        "v_proj": ((d, kv_heads * hd), _BF16), "q_norm": ((hd,), _F32),
        "k_norm": ((hd,), _F32), "o_proj": ((heads * hd, d), _BF16)}
    tree = {"embed": ((vocab, d), _BF16), "final_norm": ((d,), _F32),
            "head": ((d, vocab), _BF16)}
    for i in range(config["num_hidden_layers"]):
        tree[f"layer_{i}"] = {
            "norm1": ((d,), _F32), "norm2": ((d,), _F32),
            "mixer": dict(full if is_full_attention(config, i) else delta),
            "moe": dict(moe_block)}
    return lm_blocks.shape_tree(tree)


def random_params(config: Dict[str, Any], seed: int = 0) -> dict:
    """Seeded stand-ins for trained weights, on the default device:
    matrices normal at ``1 / sqrt(fan_in)``, norm weights small, the
    decay's ``A_log`` and ``dt_bias`` spread so that ``exp(g)`` covers
    about 0.5 to 0.999."""
    def special(leaf, k, shape, dtype):
        if leaf == "A_log":
            return jax.random.uniform(k, shape, _F32, 0.0, 1.7)
        if leaf == "dt_bias":
            return jax.random.uniform(k, shape, _F32, -6.0, -2.0)
        if dtype == _F32:  # a norm's weight
            return jax.random.uniform(k, shape, _F32, -0.1, 0.1) + (
                1.0 if leaf == "norm" else 0.0)
        return None

    return lm_blocks.draw_tree(param_shapes(config), seed, special)
