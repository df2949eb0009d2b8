"""Ouro (ByteDance, 2025-10; ``model_type: ouro``; arXiv:2510.25741) as a scoring function,
written from the model's ``config.json``, its paper and the published modelling code.
``norm(x; w) = x * rsqrt(mean(x^2) + eps) * w``. Layer ``l`` on the residual stream ``x``:

    a = Attn_l(norm(x; w1_l));  x = x + norm(a; w2_l)
    m = Mlp_l(norm(x; w3_l));   x = x + norm(m; w4_l)

* ``Attn_l(h)``: ``q, k, v = h Wq, h Wk, h Wv`` (no bias), rotary over the whole head on q
  and k (rotate-half, ``theta^(-2i/head_dim)``, position = index), scores ``q k^T /
  sqrt(head_dim)``, a causal softmax, ``concat_h(P v_h) Wo``.
* ``Mlp_l(h) = (silu(h Wg) * (h Wu)) Wd``.
* Pass ``t = 1 .. total_ut_steps`` from ``x_0 = embed[tokens]``: ``y = layer_L(...
  layer_1(x_{t-1}))`` with the same weights in every pass; ``h_t = norm(y; w_final)``; ``x_t =
  h_t``; ``g_t = h_t . w_gate + b_gate``. The head reads the last ``h``: ``logprobs`` is the
  log-probability of each token after the first given those before it.
* ``exit_pdf``: ``lambda_t = sigmoid(g_t)``, ``p_t = lambda_t * prod_{j<t} (1 - lambda_j)``, the
  last pass takes what is left; the row's output is the mean of ``p_t`` over its positions.
  ``early_exit_threshold`` is 1, so no position ever leaves before the last pass.

Plain on purpose, as ``reference/qwen3_next.py`` (whose product and rotary this uses):
float32 and ``Precision.HIGHEST``, attention as a full masked softmax in blocks of query
rows, one after the other, the head in blocks of positions. **The loop**: 192 layer
applications written out under one ``jax.jit`` would compile for many minutes, so the
reference stacks a layer's leaves on a leading axis (``layers/q_proj [48, 2048, 2048]``, asked
of the ``Net`` once, before the passes: ``Net.param`` refuses a path listed twice) and a pass
is a ``lax.scan`` of one layer's application over that axis; the passes are a Python loop. A
Python side effect inside the scanned function would run once a trace, so the row's
multiply-adds (``net.flops``) are counted outside it, for every pass. ``quant="int8"`` on the
``Net`` makes it the control. Two broken programs for the tests: ``passes=3`` (one pass too
few) and ``norm_in_loop=False`` (the next pass starts from ``y`` and not from ``h_t``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.nn import Net
from benchmarks.reference.qwen3_next import _matmul, _rotary

_HIGHEST = lax.Precision.HIGHEST
_F32 = jnp.float32


def _rms(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(_F32)


def _attention(q, k, v, block_rows: int):
    """``softmax(q k^T / sqrt(d) + causal mask) v`` per head, ``[N, T, H, d]`` each, the
    queries ``block_rows`` at a time against every key."""
    t, d = q.shape[1], q.shape[-1]
    rows = block_rows if t % block_rows == 0 else t
    position = jnp.arange(t)

    def block(lo):
        s = jnp.einsum("nqhd,nkhd->nhqk", lax.dynamic_slice_in_dim(q, lo, rows, axis=1), k,
                       precision=_HIGHEST) / math.sqrt(d)
        s = jnp.where(position[None, :] <= lo + position[:rows, None], s, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v, precision=_HIGHEST)

    o = jnp.moveaxis(lax.map(block, jnp.arange(0, t, rows)), 0, 1)  # [N, blocks, rows, H, d]
    return o.reshape(q.shape)


def forward(net: Net, tokens, config: dict, passes=None, norm_in_loop: bool = True,
            head_block: int = 2048, block_rows: int = 256):
    """``tokens`` ``[N, T]`` -> ``{"logprobs": [N, T - 1], "exit_pdf": [N, passes]}``."""
    tokens = tokens.astype(jnp.int32)
    n, t = tokens.shape
    d, f, vocab = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    depth, eps = config["num_hidden_layers"], config["rms_norm_eps"]
    heads, kv_heads, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                           config["head_dim"])
    steps = config["total_ut_steps"] if passes is None else passes
    assumed = config["assumed"]

    def matrix(name, rows, columns):
        return net.param("layers/" + name, (depth, rows, columns), "normal", 1.0 / math.sqrt(rows))

    embed = net.param("embed", (vocab, d), "normal", 1.0)
    layers = {"q_proj": matrix("q_proj", d, heads * hd), "k_proj": matrix("k_proj", d, kv_heads * hd),
              "v_proj": matrix("v_proj", d, kv_heads * hd), "o_proj": matrix("o_proj", heads * hd, d),
              "gate": matrix("gate", d, f), "up": matrix("up", d, f), "down": matrix("down", f, d)}
    for i in (1, 2, 3, 4):
        layers[f"norm{i}"] = net.param(f"layers/norm{i}", (depth, d), "uniform", 0.9, 1.1)
    w_final = net.param("final_norm", (d,), "uniform", 0.9, 1.1)
    # h has unit rms, so a uniform weight in +-gain * sqrt(3 / d) gives g a spread of gain
    reach = assumed["exit_gate_gain"] * math.sqrt(3.0 / d)
    w_gate = net.param("exit_gate/weight", (d,), "uniform", -reach, reach)
    b_gate = net.param("exit_gate/bias", (1,), "uniform", -0.5, 0.5)
    head = net.param("head", (d, vocab), "normal", assumed["head_gain"] / math.sqrt(d))

    def one_layer(x, p):
        h = _rms(x, p["norm1"], eps)
        q = _matmul(net, h, p["q_proj"]).reshape(n, t, heads, hd)
        k = _matmul(net, h, p["k_proj"]).reshape(n, t, kv_heads, hd)
        v = _matmul(net, h, p["v_proj"]).reshape(n, t, kv_heads, hd)
        q, k = _rotary(q, config["rope_theta"], hd), _rotary(k, config["rope_theta"], hd)
        k, v = (jnp.repeat(z, heads // kv_heads, axis=2) for z in (k, v))
        a = _matmul(net, _attention(q, k, v, block_rows).reshape(n, t, heads * hd), p["o_proj"])
        x = x + _rms(a, p["norm2"], eps)
        h = _rms(x, p["norm3"], eps)
        m = _matmul(net, jax.nn.silu(_matmul(net, h, p["gate"])) * _matmul(net, h, p["up"]), p["down"])
        return x + _rms(m, p["norm4"], eps), None

    x = embed[tokens].astype(_F32)
    gates = []
    for _ in range(steps):
        y, _ = lax.scan(one_layer, x, layers)
        h = _rms(y, w_final, eps)
        gates.append(jnp.sum(h * w_gate, axis=-1) + b_gate)
        x = h if norm_in_loop else y
    # a layer's four projections, its SwiGLU and the causal half of its two attention products,
    # for every pass; the gate's product a pass
    per_layer = 2 * t * (2 * d * heads * hd + 2 * d * kv_heads * hd + 3 * d * f) \
        + 2 * (t * t // 2) * heads * 2 * hd
    net.flops += steps * (depth * per_layer + 2 * t * d)

    left, pdf = jnp.ones_like(gates[0]), []
    for g in gates[:-1]:
        lam = jax.nn.sigmoid(g)
        pdf.append(lam * left)
        left = left * (1.0 - lam)
    pdf.append(left)

    h, following = h[:, :-1], tokens[:, 1:]
    net.flops += 2 * h.shape[1] * d * vocab
    logprobs = []
    for lo in range(0, h.shape[1], head_block):
        scores = jax.nn.log_softmax(_matmul(net, h[:, lo:lo + head_block], head), axis=-1)
        logprobs.append(jnp.take_along_axis(
            scores, following[:, lo:lo + head_block, None], axis=-1)[..., 0])
    return {"logprobs": jnp.concatenate(logprobs, axis=1),
            "exit_pdf": jnp.stack([jnp.mean(p, axis=-1) for p in pdf], axis=1)}
