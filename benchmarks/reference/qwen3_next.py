"""Qwen3-Next (Qwen team, 2025-09; ``model_type: qwen3_next``) as a scoring function,
written from the model's ``config.json`` and the family's published modelling code:
every layer is a token mixer and a sparse expert block, each behind a zero-centred RMS
norm and a residual; the mixer of layer ``i`` is gated softmax attention when
``(i + 1) % full_attention_interval == 0`` and the gated delta rule otherwise.

Plain on purpose: float32 and ``Precision.HIGHEST`` throughout, the delta rule as one
step per position (``lax.scan``), attention as a full masked softmax in blocks of query
rows, the routed experts as a loop over the experts held, each computed for every token
and weighted by what the router gave it (0 for most). Parameters arrive as they are
stored (bfloat16 matrices) and are widened where they are used. int32 ``[N, T]`` tokens
in, ``{"logprobs": float32 [N, T - 1]}`` out: the log-probability of each token after the
first, over the vocabulary the configuration holds.

The configuration may hold a share of the model (``experts_held``: the routed experts
``[first, end)``, ``num_experts`` of them; ``vocab_size``: a slice of the vocabulary). The
router still scores all ``router_width`` and picks ``num_experts_per_tok``; what the absent experts would
have added is left out, here as in the program.

``quant="int8"`` on the ``Net`` makes this the control: every projection, the router, the
experts and the head see their input (per tensor) and their matrix (per output column)
rounded to 255 levels. The walk also counts the multiply-adds of a row as it goes
(``net.flops``): causal attention at half the square, the routed experts at the expected
``num_experts_per_tok * held / num_experts`` a token, the delta rule at three
``dk x dv`` products a position and head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.nn import Net, _fake_int8

_HIGHEST = lax.Precision.HIGHEST
_F32 = jnp.float32


def _matmul(net: Net, x, w):
    w = w.astype(_F32)
    if net.quant == "int8":
        x = _fake_int8(x, tuple(range(x.ndim)))
        w = _fake_int8(w, (w.ndim - 2,))
    return jnp.matmul(x, w, precision=_HIGHEST)


def _proj(net: Net, x, name: str, features: int, gain: float = 1.0):
    """``x [N, T, in] @ W [in, features]``; the matrix is drawn normal at ``gain / sqrt(in)``."""
    fan_in = x.shape[-1]
    w = net.param(name, (fan_in, features), "normal", gain / math.sqrt(fan_in))
    net.flops += 2 * x.shape[1] * fan_in * features
    return _matmul(net, x, w)


def _norm(net: Net, x, name: str, eps: float, centre: float = 1.0):
    """``x / rms(x) * (centre + w)``: ``centre`` 1 is the family's zero-centred norm."""
    w = net.param(name, (x.shape[-1],), "uniform", 0.9 - centre, 1.1 - centre)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (centre + w.astype(_F32))


def _rotary(x, theta: float, rotary_dim: int):
    """Rotate-half on the first ``rotary_dim`` of each head; position = index on axis 1."""
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim))
    angle = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv_freq[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    cos = jnp.asarray(np.cos(angle), _F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), _F32)[None, :, None, :]
    turned, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rotated = jnp.concatenate([-turned[..., half:], turned[..., :half]], axis=-1)
    return jnp.concatenate([turned * cos + rotated * sin, rest], axis=-1)


def gated_attention(net: Net, x, config: dict, block_rows: int = 512):
    n, t, _ = x.shape
    heads, kv_heads, d = (config["num_attention_heads"], config["num_key_value_heads"],
                          config["head_dim"])
    eps = config["rms_norm_eps"]
    with net.scope("GatedAttention"):
        qg = _proj(net, x, "q_proj", heads * 2 * d).reshape(n, t, heads, 2 * d)
        k = _proj(net, x, "k_proj", kv_heads * d).reshape(n, t, kv_heads, d)
        v = _proj(net, x, "v_proj", kv_heads * d).reshape(n, t, kv_heads, d)
        q, gate = qg[..., :d], qg[..., d:]
        rotary_dim = int(d * config["partial_rotary_factor"])
        q = _rotary(_norm(net, q, "q_norm", eps), config["rope_theta"], rotary_dim)
        k = _rotary(_norm(net, k, "k_norm", eps), config["rope_theta"], rotary_dim)
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
        position = jnp.arange(t)
        out = []
        for lo in range(0, t, block_rows):
            hi = min(lo + block_rows, t)
            s = jnp.einsum("nqhd,nkhd->nhqk", q[:, lo:hi], k, precision=_HIGHEST)
            s = s / math.sqrt(d)
            s = jnp.where(position[None, :] <= position[lo:hi, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            out.append(jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=_HIGHEST))
        o = jnp.concatenate(out, axis=1) * jax.nn.sigmoid(gate)
        net.flops += 4 * t * t // 2 * d * heads
        return _proj(net, o.reshape(n, t, heads * d), "o_proj", x.shape[-1])


def delta_rule_recurrence(q, k, v, g, beta):
    """The gated delta rule, one position at a time. ``q``, ``k``: ``[N, T, H, dk]``;
    ``v``: ``[N, T, H, dv]``; ``g`` (log-decay) and ``beta``: ``[N, T, H]``."""
    n, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("nhkv,nhk->nhv", state, k_t, precision=_HIGHEST)
        delta = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, q_t, precision=_HIGHEST)

    state0 = jnp.zeros((n, h, dk, v.shape[-1]), _F32)
    _, o = lax.scan(step, state0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def gated_delta_net(net: Net, x, config: dict, use_decay: bool = True):
    n, t, hidden = x.shape
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    width = config["linear_conv_kernel_dim"]
    with net.scope("GatedDeltaNet"):
        qkvz = _proj(net, x, "in_proj_qkvz", 2 * key_dim + 2 * value_dim)
        ba = _proj(net, x, "in_proj_ba", 2 * hv)
        qkv, z = qkvz[..., :2 * key_dim + value_dim], qkvz[..., 2 * key_dim + value_dim:]
        conv = net.param("conv", (width, qkv.shape[-1]), "normal", 1.0 / math.sqrt(width))
        padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
        qkv = sum(padded[:, j:j + t] * conv[j].astype(_F32) for j in range(width))
        net.flops += 2 * t * width * qkv.shape[-1]
        qkv = jax.nn.silu(qkv)
        q = qkv[..., :key_dim].reshape(n, t, hk, dk)
        k = qkv[..., key_dim:2 * key_dim].reshape(n, t, hk, dk)
        v = qkv[..., 2 * key_dim:].reshape(n, t, hv, dv)
        unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        q = jnp.repeat(unit(q), hv // hk, axis=2) / math.sqrt(dk)
        k = jnp.repeat(unit(k), hv // hk, axis=2)
        a_log = net.param("A_log", (hv,), "uniform", *config["assumed"]["A_log_range"])
        dt_bias = net.param("dt_bias", (hv,), "uniform", *config["assumed"]["dt_bias_range"])
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(ba[..., hv:] + dt_bias.astype(_F32))
        if not use_decay:  # a broken program for the tests: the decay exp(g) left out
            g = jnp.zeros_like(g)
        o = delta_rule_recurrence(q, k, v, g, beta)
        net.flops += 3 * 2 * t * hv * dk * dv
        o = _norm(net, o, "norm", config["rms_norm_eps"], centre=0.0)
        o = o * jax.nn.silu(z.reshape(n, t, hv, dv))
        return _proj(net, o.reshape(n, t, value_dim), "out_proj", hidden)


def _swiglu(net: Net, x, w_gate, w_up, w_down):
    hidden = jax.nn.silu(_matmul(net, x, w_gate)) * _matmul(net, x, w_up)
    return _matmul(net, hidden, w_down)


def sparse_moe(net: Net, x, config: dict):
    n, t, d = x.shape
    # the file's num_experts counts the experts held here; the router keeps its width
    experts = config.get("router_width", config["num_experts"])
    per_token = config["num_experts_per_tok"]
    first, end = config.get("experts_held", (0, experts))
    held, f, fs = end - first, config["moe_intermediate_size"], config["shared_expert_intermediate_size"]
    with net.scope("SparseMoe"):
        p = jax.nn.softmax(_proj(net, x, "router", experts), axis=-1)
        top_p, top_i = lax.top_k(p, per_token)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        w_gate = net.param("experts_gate", (held, d, f), "normal", 1.0 / math.sqrt(d))
        w_up = net.param("experts_up", (held, d, f), "normal", 1.0 / math.sqrt(d))
        w_down = net.param("experts_down", (held, f, d), "normal", 1.0 / math.sqrt(f))

        def one_expert(total, xs):
            index, gate, up, down = xs
            weight = jnp.sum(jnp.where(top_i == index, top_p, 0.0), axis=-1)
            return total + weight[..., None] * _swiglu(net, x, gate, up, down), None

        routed, _ = lax.scan(one_expert, jnp.zeros_like(x),
                             (jnp.arange(first, end), w_gate, w_up, w_down))
        net.flops += int(2 * t * per_token * held / experts * 3 * d * f)
        shared = _swiglu(net, x,
                         net.param("shared_gate", (d, fs), "normal", 1.0 / math.sqrt(d)),
                         net.param("shared_up", (d, fs), "normal", 1.0 / math.sqrt(d)),
                         net.param("shared_down", (fs, d), "normal", 1.0 / math.sqrt(fs)))
        w_s = net.param("shared_router", (d,), "normal", 1.0 / math.sqrt(d))
        net.flops += 2 * t * (3 * d * fs + d)
        # per row, the assignments each held expert received: what the program counts too
        counts = jnp.sum(top_i.reshape(n, -1, 1) == jnp.arange(first, end), axis=1)
        return routed + jax.nn.sigmoid(_matmul(net, x, w_s[:, None])) * shared, counts


def layer(net: Net, x, config: dict, index: int, use_decay: bool = True):
    eps = config["rms_norm_eps"]
    with net.scope("Layer"):
        h = _norm(net, x, "norm1", eps)
        if (index + 1) % config["full_attention_interval"] == 0:
            x = x + gated_attention(net, h, config)
        else:
            x = x + gated_delta_net(net, h, config, use_decay)
        y, counts = sparse_moe(net, _norm(net, x, "norm2", eps), config)
        return x + y, counts


def forward(net: Net, tokens, config: dict, use_decay: bool = True, head_block: int = 2048):
    """``tokens`` ``[N, T]`` -> ``{"logprobs": [N, T - 1], "routing": [N, layers, held]}``
    (per row and layer the assignments each held expert received); where the
    configuration's ``head`` is ``logits``, ``"logits": [N, T - 1, vocabulary]`` beside them."""
    tokens = tokens.astype(jnp.int32)
    d, vocab = config["hidden_size"], config["vocab_size"]
    embed = net.param("embed", (vocab, d), "normal", 1.0)
    x = embed[tokens].astype(_F32)
    routing = []
    for index in range(config["num_hidden_layers"]):
        x, counts = layer(net, x, config, index, use_decay)
        routing.append(counts)
    x = _norm(net, x, "final_norm", config["rms_norm_eps"])[:, :-1]
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
           "routing": jnp.stack(routing, axis=1).astype(jnp.int32)}  # [N, layers, held]
    if all_logits:
        out["logits"] = jnp.concatenate(all_logits, axis=1)
    return out
