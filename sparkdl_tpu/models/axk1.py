"""A.X-K1 (SK Telecom, 2026-01; ``model_type: axk1``) as a scoring
function over token rows.

The second token model here, of the DeepSeek-V2/V3 family (``hf``
below is that family's published modelling code, whose keys the
model's ``config.json`` uses): :func:`model_function` builds a
:class:`~sparkdl_tpu.graph.function.ModelFunction` with input ``tokens``
(int32 ``[T]`` a row) and output ``logprobs`` (float32 ``[T - 1]``) that
goes through ``TensorTransformer`` as ``models/qwen3_next.py``'s does::

    mf = axk1.model_function(config, params, seq_len=8192)
    TensorTransformer(modelFunction=mf, inputMapping={"tokens": "tokens"},
                      outputMapping={"logprobs": "logprobs"}, batchSize=2)

Every layer is latent attention and a feed-forward, each behind a plain
RMS norm (``x / rms(x) * w``) and a residual:

* **Latent attention** (MLA; ``LatentAttention_<i>``). Queries and keys
  come through low-rank projections, each with a norm behind it:
  ``c_q = norm(h W_qa)`` (``q_lora_rank``), ``q = c_q W_qb`` per head
  ``[q_nope | q_rope]``; ``[c_kv | k_rope] = h W_kva`` (``kv_lora_rank``
  and one rotary key head that every query head shares), ``norm(c_kv)
  W_kvb`` per head ``[k_nope | v]``. Rotary (YaRN's frequencies,
  :func:`yarn_inv_freq`) turns ``q_rope`` and ``k_rope``; all of that
  lies under the scope ``latent_proj``. The scores ``(q_nope . k_nope +
  q_rope . k_rope) * scale`` and the causal softmax are
  ``ops/attention.py``'s kernel ``attention``, which takes the rotary
  pair beside the other and values narrower than keys, reads ``k_nope``
  and ``v`` out of the product ``norm(c_kv) W_kvb`` where it lies and
  writes ``o_proj``'s operand as ``o_proj`` reads it; then ``o_proj``.
  This is MLA's decompressed form, the one a prefill runs: per-head keys
  and values are rebuilt from the latent. The absorbed form, which a
  latent cache needs, waits for a path that keeps state between calls.
* **Feed-forward**: a dense SwiGLU (``DenseMlp_<i>``) in the first
  ``first_k_dense_replace`` layers; in the rest a sparse expert block
  (``SparseMoe_<i>``): the router scores every expert with a sigmoid,
  the ``num_experts_per_tok`` largest are renormalised and scaled by
  ``routed_scaling_factor`` (``ops/moe.py::route``), ``ops/moe.py``
  computes the part of the experts this chip holds (``experts_held``),
  and a shared expert with no gate is added once.

``config`` holds the published keys; ``router_width`` (else
``n_routed_experts``) is the router's width, ``experts_held = [first,
end)`` the experts whose matrices the tree holds, all of them where the
key is absent. ``topk_method: "none"`` is read as a plain top-k of the
sigmoid scores: no group limit (``n_group`` and ``topk_group`` go
unused) and no selection bias (the family adds one only under
``noaux_tc``). ``hf`` takes the rotary columns pairwise-adjacent; here
they are rotate-half, a permutation of the columns of ``W_qb`` and
``W_kva``.

With ``routing_stats=True`` the function has a second output,
``routing`` (int32 ``[routed layers, 1 + held]`` a row: a row for each
layer that routes, none for a dense one), which
``ops/moe.py::record_routing`` sums into the registry's ``moe.*``
counters.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.models import lm_blocks
from sparkdl_tpu.models.lm_blocks import BF16, F32, dot, rms_norm
from sparkdl_tpu.ops import attention as attention_op
from sparkdl_tpu.ops import moe


def router_width(config: Dict[str, Any]) -> int:
    return int(config.get("router_width", config["n_routed_experts"]))


def experts_held(config: Dict[str, Any]) -> tuple:
    return lm_blocks.experts_held(config, router_width(config))


def is_routed(config: Dict[str, Any], layer: int) -> bool:
    return layer >= int(config["first_k_dense_replace"])


# -- YaRN ---------------------------------------------------------------------

def yarn_inv_freq(config: Dict[str, Any]) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies under YaRN
    (float64): a pair that turns more than ``beta_fast`` times within the
    original context keeps its frequency ``f_i = theta^(-2i/d)``, one that
    turns fewer than ``beta_slow`` times is slowed by ``factor``, and those
    between are blended linearly in ``i``."""
    d = int(config["qk_rope_head_dim"])
    f = float(config["rope_theta"]) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    scaling = config.get("rope_scaling")
    if not scaling:
        return f
    if scaling["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {scaling['type']!r}")

    def turns_at(beta):  # the pair that turns `beta` times in the original context
        return (d * math.log(scaling["original_max_position_embeddings"]
                             / (beta * 2 * math.pi))
                / (2 * math.log(config["rope_theta"])))

    low = max(math.floor(turns_at(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_at(scaling["beta_slow"])), d - 1)
    ramp = (np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 0.001)
    keep = 1.0 - np.clip(ramp, 0.0, 1.0)
    return f / scaling["factor"] * (1.0 - keep) + f * keep


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(config: Dict[str, Any]) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times YaRN's ``mscale^2`` where
    ``mscale_all_dim`` is set. (The factor on the rotary's cos and sin,
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, is 1 for
    this model and must be: nothing here applies another.)"""
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    scaling = config.get("rope_scaling")
    if scaling:
        all_dim = scaling.get("mscale_all_dim", 0)
        if _yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) != \
                _yarn_mscale(scaling["factor"], all_dim):
            raise ValueError("rope_scaling's mscale and mscale_all_dim differ: "
                             "cos and sin would need a factor")
        if all_dim:
            scale *= _yarn_mscale(scaling["factor"], all_dim) ** 2
    return scale


# -- the blocks ---------------------------------------------------------------

def latent_attention(p, x, config):
    b, t, _ = x.shape
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    eps = config["rms_norm_eps"]
    inv_freq = yarn_inv_freq(config)
    with jax.named_scope("latent_proj"):
        c_q = rms_norm(dot(x, p["q_a_proj"]), p["q_a_norm"], eps)
        q = dot(c_q, p["q_b_proj"]).reshape(b, t, heads, dn + dr)
        kv_a = dot(x, p["kv_a_proj"])  # [c_kv | k_rope]
        c_kv = rms_norm(kv_a[..., :rank], p["kv_a_norm"], eps)
        kv = dot(c_kv, p["kv_b_proj"]).reshape(b, t, heads, dn + dv)
        q_rope = lm_blocks.rotate_half(q[..., dn:], inv_freq)
        k_rope = lm_blocks.rotate_half(kv_a[:, :, None, rank:], inv_freq)
    # the heads come from slices of two products, nothing a heads-first
    # transpose could ride in: the kernel reads them where they lie, k_nope
    # and v out of `kv` itself, and writes `o_proj`'s operand in its type
    o = attention_op.causal_attention(
        q[..., :dn], attention_op.HeadSlice(kv, 0, dn),
        attention_op.HeadSlice(kv, dn, dv), scale=softmax_scale(config),
        rope=(q_rope, k_rope), dtype=p["q_b_proj"].dtype,
        out_dtype=p["o_proj"].dtype, in_place=("q", "k", "v"))
    return dot(o.reshape(b, t, heads * dv), p["o_proj"])


def sparse_moe(p, x, config):
    """``(y, experts)``: the block's output for ``x`` (``[B, T, D]``) and
    the experts each token chose (``[B * T, k]``)."""
    b, t, d = x.shape
    first, _ = experts_held(config)
    flat = x.reshape(b * t, d)
    experts, weights = moe.route(
        dot(flat, p["router"]), config["num_experts_per_tok"],
        scoring=config["scoring_func"], scale=config["routed_scaling_factor"])
    routed, _ = moe.held_experts_ffn(
        flat, experts, weights, p["experts_gate"], p["experts_up"],
        p["experts_down"], first=first)
    shared = lm_blocks.swiglu(flat, p["shared_gate"], p["shared_up"],
                              p["shared_down"])
    return (routed + shared).reshape(b, t, d), experts


def final_hidden(params, tokens, config, routing_stats: bool = False):
    """The residual stream after the last layer and the final norm
    (float32 ``[B, T, D]``), and per layer that routes the held experts'
    counts (``[B, held]`` each; empty without ``routing_stats``)."""
    eps = config["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    routing = []
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        with jax.named_scope(f"LatentAttention_{i}"):
            x = x + latent_attention(p["mixer"], rms_norm(x, p["norm1"], eps),
                                     config)
        h = rms_norm(x, p["norm2"], eps)
        if is_routed(config, i):
            with jax.named_scope(f"SparseMoe_{i}"):
                y, experts = sparse_moe(p["moe"], h, config)
                if routing_stats:
                    routing.append(lm_blocks.held_counts(
                        experts, x.shape[0], experts_held(config)))
        else:
            with jax.named_scope(f"DenseMlp_{i}"):
                y = lm_blocks.swiglu(h, p["mlp"]["gate"], p["mlp"]["up"],
                                     p["mlp"]["down"])
        x = x + y
    return rms_norm(x, params["final_norm"], eps), routing


def forward(params, tokens, config, routing_stats: bool = False):
    """``tokens`` int32 ``[B, T]`` -> ``{"logprobs": float32 [B, T - 1]}``
    and, with ``routing_stats``, ``"routing"`` int32 ``[B, routed layers,
    1 + held]``."""
    x, routing = final_hidden(params, tokens, config, routing_stats)
    out = {"logprobs": lm_blocks.score_head(x, tokens, params["head"])}
    if routing_stats:
        out["routing"] = lm_blocks.routing_output(routing)
    return out


# -- the ModelFunction --------------------------------------------------------

def model_function(config: Dict[str, Any], params, *, seq_len: int,
                   routing_stats: bool = False) -> ModelFunction:
    """The scoring function over rows of ``seq_len`` tokens; ``params`` is
    the tree :func:`param_shapes` describes."""
    return lm_blocks.scoring_function(
        functools.partial(forward, routing_stats=routing_stats), config, params,
        seq_len=seq_len, name="AXK1",
        outputs=["logprobs"] + (["routing"] if routing_stats else []))


def param_shapes(config: Dict[str, Any]) -> dict:
    """The parameter tree, a ``jax.ShapeDtypeStruct`` for each leaf."""
    d, vocab = config["hidden_size"], config["vocab_size"]
    first, end = experts_held(config)
    held, f = end - first, config["moe_intermediate_size"]
    fs = f * config["n_shared_experts"]
    fd = config["intermediate_size"]
    heads = config["num_attention_heads"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    mixer = {
        "q_a_proj": ((d, q_rank), BF16), "q_a_norm": ((q_rank,), F32),
        "q_b_proj": ((q_rank, heads * (dn + dr)), BF16),
        "kv_a_proj": ((d, kv_rank + dr), BF16), "kv_a_norm": ((kv_rank,), F32),
        "kv_b_proj": ((kv_rank, heads * (dn + dv)), BF16),
        "o_proj": ((heads * dv, d), BF16)}
    mlp = {"gate": ((d, fd), BF16), "up": ((d, fd), BF16), "down": ((fd, d), BF16)}
    moe_block = {
        "router": ((d, router_width(config)), BF16),
        "experts_gate": ((held, d, f), BF16), "experts_up": ((held, d, f), BF16),
        "experts_down": ((held, f, d), BF16),
        "shared_gate": ((d, fs), BF16), "shared_up": ((d, fs), BF16),
        "shared_down": ((fs, d), BF16)}
    tree = {"embed": ((vocab, d), BF16), "final_norm": ((d,), F32),
            "head": ((d, vocab), BF16)}
    for i in range(config["num_hidden_layers"]):
        tree[f"layer_{i}"] = {
            "norm1": ((d,), F32), "norm2": ((d,), F32), "mixer": dict(mixer),
            **({"moe": dict(moe_block)} if is_routed(config, i)
               else {"mlp": dict(mlp)})}
    return lm_blocks.shape_tree(tree)


def random_params(config: Dict[str, Any], seed: int = 0) -> dict:
    """Seeded stand-ins for trained weights, on the default device:
    matrices normal at ``1 / sqrt(fan_in)``, norm weights within 0.1 of 1."""
    def special(leaf, k, shape, dtype):
        if dtype == F32:  # a norm's weight
            return jax.random.uniform(k, shape, F32, 0.9, 1.1)
        return None

    return lm_blocks.draw_tree(param_shapes(config), seed, special)
