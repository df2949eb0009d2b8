"""A.X-K1 (SK Telecom, 2026-01; ``model_type: axk1``) as a scoring function, written
from the model's ``config.json`` and the published modelling code of the DeepSeek-V2/V3
family, whose keys it uses. ``norm(x; w) = x * rsqrt(mean(x^2) + eps) * w``; layer ``i``
is ``x += MLA(norm(x)); x += FFN_i(norm(x))``; then a final norm and the head.

* MLA, ``h [T, hidden]``: ``c_q = norm(h W_qa)``; ``q = c_q W_qb``, per head ``[q_nope |
  q_rope]``. ``[c_kv | k_rope] = h W_kva``; ``norm(c_kv) W_kvb``, per head ``[k_nope |
  v]``. Rotary (rotate-half, YaRN's frequencies) on ``q_rope`` per head and on ``k_rope``,
  one head for all. Scores ``(q_nope . k_nope + q_rope . k_rope) * (dn + dr)^-0.5 *
  mscale^2``, a causal softmax, ``o = concat_h(P v_h) W_o``. No bias.
* ``FFN_i``, ``i < first_k_dense_replace``: ``W_d (silu(W_g h) * W_u h)``. Else ``s =
  sigmoid(h W_r)`` over all the router's outputs, the ``num_experts_per_tok`` largest,
  ``w_j = s_j / (their sum + 1e-20) * routed_scaling_factor``, ``y = sum over the chosen j
  held here of w_j E_j(h) + S(h)``, ``E_j`` and the shared ``S`` SwiGLU, ``S`` ungated.

Plain on purpose, as ``reference/qwen3_next.py`` (whose product, projection, norm and
SwiGLU this uses): float32 and ``Precision.HIGHEST``, attention as a full masked softmax
in blocks of query rows, one after the other, with the shared rotary key repeated for
every head, the dense layer in blocks of positions, each held expert computed for every
token and weighted by what the router gave it. ``quant="int8"`` on the ``Net`` makes it
the control; the walk counts a row's multiply-adds (``net.flops``). The configuration may
hold a share (``experts_held``, ``vocab_size``): the router keeps its ``router_width``, and
what the absent experts would have added is left out, here as in the program.
``use_rope_key=False`` is a broken program for the tests: the shared rotary key's part of
the scores left out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.nn import Net
from benchmarks.reference.qwen3_next import _matmul, _norm, _proj, _swiglu

_HIGHEST = lax.Precision.HIGHEST
_F32 = jnp.float32


def _plain_norm(net: Net, x, name: str, eps: float):
    return _norm(net, x, name, eps, centre=0.0)  # x / rms(x) * w, w drawn in 0.9..1.1


def yarn_inv_freq(config: dict) -> np.ndarray:
    """The rotary frequencies as the published ``YarnRotaryEmbedding`` computes them."""
    dim, base = config["qk_rope_head_dim"], config["rope_theta"]
    scaling = config["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / scaling["factor"]

    def correction_dim(rotations):
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def _mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rotary(x, inv_freq: np.ndarray):
    """Rotate-half over the whole last axis; position = index on axis 1."""
    angle = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv_freq[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    cos = jnp.asarray(np.cos(angle), _F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), _F32)[None, :, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def latent_attention(net: Net, x, config: dict, use_rope_key: bool = True,
                     block_rows: int = 256):
    n, t, _ = x.shape
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    eps, scaling = config["rms_norm_eps"], config["rope_scaling"]
    inv_freq = yarn_inv_freq(config)
    # cos and sin carry mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    rotary_gain = (_mscale(scaling["factor"], scaling["mscale"])
                   / _mscale(scaling["factor"], scaling["mscale_all_dim"]))
    scale = (dn + dr) ** -0.5 * _mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    with net.scope("LatentAttention"):
        c_q = _plain_norm(net, _proj(net, x, "q_a_proj", config["q_lora_rank"]), "q_a_norm", eps)
        q = _proj(net, c_q, "q_b_proj", heads * (dn + dr)).reshape(n, t, heads, dn + dr)
        kv_a = _proj(net, x, "kv_a_proj", rank + dr)
        c_kv = _plain_norm(net, kv_a[..., :rank], "kv_a_norm", eps)
        kv = _proj(net, c_kv, "kv_b_proj", heads * (dn + dv)).reshape(n, t, heads, dn + dv)
        q_rope = _rotary(q[..., dn:], inv_freq) * rotary_gain
        k_rope = _rotary(kv_a[:, :, None, rank:], inv_freq) * rotary_gain
        if not use_rope_key:
            k_rope = jnp.zeros_like(k_rope)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_rope, heads, axis=2)], axis=-1)
        v = kv[..., dn:]
        rows = block_rows if t % block_rows == 0 else t
        position = jnp.arange(t)

        def block(lo):  # the queries lo .. lo + rows against every key
            s = jnp.einsum("nqhd,nkhd->nhqk", lax.dynamic_slice_in_dim(q, lo, rows, axis=1), k,
                           precision=_HIGHEST) * scale
            s = jnp.where(position[None, :] <= lo + position[:rows, None], s, -jnp.inf)
            return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v,
                              precision=_HIGHEST)

        o = jnp.moveaxis(lax.map(block, jnp.arange(0, t, rows)), 0, 1)
        net.flops += 2 * (t * t // 2) * heads * (dn + dr + dv)
        return _proj(net, o.reshape(n, t, heads * dv), "o_proj", x.shape[-1])


def _swiglu_params(net: Net, prefix: str, d: int, f: int):
    return (net.param(prefix + "gate", (d, f), "normal", 1.0 / math.sqrt(d)),
            net.param(prefix + "up", (d, f), "normal", 1.0 / math.sqrt(d)),
            net.param(prefix + "down", (f, d), "normal", 1.0 / math.sqrt(f)))


def dense_mlp(net: Net, x, config: dict, block_rows: int = 2048):
    """In blocks of positions: the hidden activations of a whole row are ``T x 18,432``."""
    d, f = x.shape[-1], config["intermediate_size"]
    with net.scope("DenseMlp"):
        w = _swiglu_params(net, "", d, f)
        net.flops += 2 * x.shape[1] * 3 * d * f
        return jnp.concatenate([_swiglu(net, x[:, lo:lo + block_rows], *w)
                                for lo in range(0, x.shape[1], block_rows)], axis=1)


def sparse_moe(net: Net, x, config: dict):
    n, t, d = x.shape
    # the file's n_routed_experts counts the experts held here; the router keeps its width
    experts = config.get("router_width", config["n_routed_experts"])
    per_token = config["num_experts_per_tok"]
    first, end = config.get("experts_held", (0, experts))
    held, f = end - first, config["moe_intermediate_size"]
    fs = f * config["n_shared_experts"]
    with net.scope("SparseMoe"):
        s = jax.nn.sigmoid(_proj(net, x, "router", experts))
        top_s, top_i = lax.top_k(s, per_token)
        top_w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
        top_w = top_w * config["routed_scaling_factor"]
        w_gate = net.param("experts_gate", (held, d, f), "normal", 1.0 / math.sqrt(d))
        w_up = net.param("experts_up", (held, d, f), "normal", 1.0 / math.sqrt(d))
        w_down = net.param("experts_down", (held, f, d), "normal", 1.0 / math.sqrt(f))

        def one_expert(total, xs):
            index, gate, up, down = xs
            weight = jnp.sum(jnp.where(top_i == index, top_w, 0.0), axis=-1)
            return total + weight[..., None] * _swiglu(net, x, gate, up, down), None

        routed, _ = lax.scan(one_expert, jnp.zeros_like(x),
                             (jnp.arange(first, end), w_gate, w_up, w_down))
        net.flops += int(2 * t * per_token * held / experts * 3 * d * f)
        shared = _swiglu(net, x, *_swiglu_params(net, "shared_", d, fs))
        net.flops += 2 * t * 3 * d * fs
        # per row, the assignments each held expert received: what the program counts too
        counts = jnp.sum(top_i.reshape(n, -1, 1) == jnp.arange(first, end), axis=1)
        return routed + shared, counts


def forward(net: Net, tokens, config: dict, use_rope_key: bool = True, head_block: int = 2048):
    """``tokens`` ``[N, T]`` -> ``{"logprobs": [N, T - 1], "routing": [N, routed layers,
    held]}`` (per row and layer that routes, the assignments each held expert received);
    where the configuration's ``head`` is ``logits``, ``"logits": [N, T - 1, vocabulary]``."""
    tokens = tokens.astype(jnp.int32)
    d, vocab, eps = config["hidden_size"], config["vocab_size"], config["rms_norm_eps"]
    embed = net.param("embed", (vocab, d), "normal", 1.0)
    x = embed[tokens].astype(_F32)
    routing = []
    for index in range(config["num_hidden_layers"]):
        with net.scope("Layer"):
            x = x + latent_attention(net, _plain_norm(net, x, "norm1", eps), config, use_rope_key)
            h = _plain_norm(net, x, "norm2", eps)
            if index < config["first_k_dense_replace"]:
                x = x + dense_mlp(net, h, config)
            else:
                y, counts = sparse_moe(net, h, config)
                x = x + y
                routing.append(counts)
    x = _plain_norm(net, x, "final_norm", eps)[:, :-1]
    head = net.param("head", (d, vocab), "normal", config["assumed"]["head_gain"] / math.sqrt(d))
    net.flops += 2 * x.shape[1] * d * vocab
    following = tokens[:, 1:]
    logprobs, all_logits = [], []
    for lo in range(0, x.shape[1], head_block):
        logits = _matmul(net, x[:, lo:lo + head_block], head)
        scores = jax.nn.log_softmax(logits, axis=-1)
        logprobs.append(jnp.take_along_axis(
            scores, following[:, lo:lo + head_block, None], axis=-1)[..., 0])
        if config["head"] == "logits":
            all_logits.append(logits)
    out = {"logprobs": jnp.concatenate(logprobs, axis=1),
           "routing": jnp.stack(routing, axis=1).astype(jnp.int32)}
    if all_logits:
        out["logits"] = jnp.concatenate(all_logits, axis=1)
    return out
