"""Ouro (ByteDance, 2025-10; ``model_type: ouro``; arXiv:2510.25741) as a
scoring function over token rows.

The third token model here and the first that is a loop: its
``num_hidden_layers`` layers are applied ``total_ut_steps`` times in a
row over the same weights, with the final norm and a one-output exit
gate after every pass. :func:`model_function` builds a
:class:`~sparkdl_tpu.graph.function.ModelFunction` with input ``tokens``
(int32 ``[T]`` a row) and outputs ``logprobs`` (float32 ``[T - 1]``) and
``exit_pdf`` (float32 ``[total_ut_steps]``) that goes through
``TensorTransformer`` as the two other token models' do; a caller that
wants the scores alone leaves ``exit_pdf`` unmapped::

    mf = ouro.model_function(config, params, seq_len=4096)
    TensorTransformer(modelFunction=mf, inputMapping={"tokens": "tokens"},
                      outputMapping={"logprobs": "logprobs"}, batchSize=2)

``norm(x; w) = x * rsqrt(mean(x^2) + eps) * w``. A layer has four norms,
one before and one after each block (``hf`` below is the published
modelling code)::

    a = Attn(norm(x; w1));  x = x + norm(a; w2)
    m = Mlp(norm(x; w3));   x = x + norm(m; w4)

``Attn``: ``q, k, v = h Wq, h Wk, h Wv`` with no bias, rotate-half rotary
over the whole head on q and k, a causal softmax of ``q k^T /
sqrt(head_dim)`` (``ops/attention.py``'s kernel ``attention``), then
``Wo``. ``Mlp`` is a SwiGLU. Pass ``t`` runs every layer on ``x_{t-1}``
(``x_0`` the embedding's rows), ``h_t = norm(y; w_final)`` is what pass
``t + 1`` starts from, and ``g_t = h_t . w_gate + b_gate`` is the exit
gate's number for each position. The head scores ``h`` of the last pass.

The program is a loop on the device and not 192 layers written out: the
parameter tree holds each of a layer's eleven leaves once, with a leading
axis of ``num_hidden_layers`` (``layers/q_proj [48, 2048, 2048]``), a
pass is a ``lax.scan`` of one layer body over that axis, and the passes
are a ``lax.scan`` of ``total_ut_steps`` turns that hands the same tree
to every pass. The compiled program holds one layer body, and no weight
is copied for a pass. Named scopes carry no layer index: ``ut_loop``
round the passes; in the body ``sandwich_norm`` (the four norms),
``attn_proj`` (the four projections and the rotary), ``attention`` (the
kernel's own name) and ``mlp``; ``exit_gate`` and ``Head`` outside it.
(On the chip XLA merges the rotary of q and k into one multi-output
fusion that keeps no scope's name: a reader of the device trace by scope
finds the four products under ``attn_proj`` and not the rotary;
``docs/OBSERVABILITY.md``.)

``early_exit_threshold`` 1 is the only value served: the cumulative exit
probability reaches 1 at the last pass only, so every position takes
every pass. Leaving the loop early for some rows of a device batch needs
rows of unequal work in one step, which the runner does not have.

The second output, ``exit_pdf`` (float32 ``[total_ut_steps]`` a row),
is the exit distribution: with ``lambda_t = sigmoid(g_t)``, ``p_t =
lambda_t * prod_{j<t} (1 - lambda_j)`` and the last pass takes what is
left; the row's output is the mean of ``p_t`` over its positions. The
gate's numbers come out of the loop either way, so the output has no
switch. :func:`record_exit` sums a window of it into the registry's
``loop.*`` names.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.models import lm_blocks
from sparkdl_tpu.models.lm_blocks import BF16, F32, dot, rms_norm
from sparkdl_tpu.ops import attention as attention_op


def rotary_inv_freq(config: Dict[str, Any]) -> np.ndarray:
    """The ``head_dim / 2`` rotary frequencies ``theta^(-2i/head_dim)``
    (float64); no scaling is served."""
    if config.get("rope_scaling"):
        raise ValueError(f"rope_scaling {config['rope_scaling']!r}")
    d = int(config["head_dim"])
    return float(config["rope_theta"]) ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def layer(p, x, config):
    """One layer on the residual stream ``x`` (float32 ``[B, T, D]``);
    ``p`` holds the layer's eleven leaves, without the stacked axis."""
    b, t, _ = x.shape
    heads, kv_heads, d = (config["num_attention_heads"],
                          config["num_key_value_heads"], config["head_dim"])
    eps = config["rms_norm_eps"]
    inv_freq = rotary_inv_freq(config)
    with jax.named_scope("sandwich_norm"):
        h = rms_norm(x, p["norm1"], eps)
    with jax.named_scope("attn_proj"):
        q = dot(h, p["q_proj"]).reshape(b, t, heads, d)
        k = dot(h, p["k_proj"]).reshape(b, t, kv_heads, d)
        v = dot(h, p["v_proj"]).reshape(b, t, kv_heads, d)
        q = lm_blocks.rotate_half(q, inv_freq)
        k = lm_blocks.rotate_half(k, inv_freq)
    # q, k and v stay heads-first copies: XLA keeps the products' results
    # with the positions minor, and the transposes ride in the rotary's and
    # v's own fusions (read in place they cost three copies more)
    o = attention_op.causal_attention(q, k, v, scale=d ** -0.5,
                                      dtype=p["q_proj"].dtype,
                                      out_dtype=p["o_proj"].dtype)
    with jax.named_scope("attn_proj"):
        a = dot(o.reshape(b, t, heads * d), p["o_proj"])
    with jax.named_scope("sandwich_norm"):
        x = x + rms_norm(a, p["norm2"], eps)
        h = rms_norm(x, p["norm3"], eps)
    with jax.named_scope("mlp"):
        m = lm_blocks.swiglu(h, p["gate"], p["up"], p["down"])
    with jax.named_scope("sandwich_norm"):
        return x + rms_norm(m, p["norm4"], eps)


def final_hidden(params, tokens, config):
    """``(h, g)``: the last pass's final-norm output (float32 ``[B, T,
    D]``) and every pass's gate numbers (float32 ``[passes, B, T]``)."""
    eps = config["rms_norm_eps"]
    gate = params["exit_gate"]

    def one_layer(x, p):
        return layer(p, x, config), None

    def one_pass(x, _):
        y, _ = jax.lax.scan(one_layer, x, params["layers"])
        h = rms_norm(y, params["final_norm"], eps)
        with jax.named_scope("exit_gate"):
            g = jnp.sum(h * gate["weight"], axis=-1) + gate["bias"]
        return h, g

    x = params["embed"][tokens].astype(F32)
    with jax.named_scope("ut_loop"):
        return jax.lax.scan(one_pass, x, None,
                            length=int(config["total_ut_steps"]))


def exit_pdf(g):
    """Gate numbers ``[passes, B, T]`` -> the exit distribution's mean over
    a row's positions, float32 ``[B, passes]`` (a row sums to 1)."""
    with jax.named_scope("exit_gate"):
        stay = 1.0 - jax.nn.sigmoid(g[:-1])
        # what is left before each pass: 1, (1 - l_1), (1 - l_1)(1 - l_2), ...
        left = jnp.concatenate([jnp.ones_like(g[:1]), jnp.cumprod(stay, axis=0)])
        p = jnp.concatenate([left[:-1] * (1.0 - stay), left[-1:]])
        return jnp.mean(p, axis=-1).T


def forward(params, tokens, config):
    """``tokens`` int32 ``[B, T]`` -> ``{"logprobs": float32 [B, T - 1],
    "exit_pdf": float32 [B, passes]}``."""
    if config.get("early_exit_threshold", 1) != 1:
        raise ValueError("early_exit_threshold other than 1: no row leaves "
                         "the loop early here")
    h, g = final_hidden(params, tokens, config)
    return {"logprobs": lm_blocks.score_head(h, tokens, params["head"]),
            "exit_pdf": exit_pdf(g)}


# -- the ModelFunction --------------------------------------------------------

def model_function(config: Dict[str, Any], params, *,
                   seq_len: int) -> ModelFunction:
    """The scoring function over rows of ``seq_len`` tokens; ``params`` is
    the tree :func:`param_shapes` describes."""
    return lm_blocks.scoring_function(
        forward, config, params, seq_len=seq_len, name="Ouro",
        outputs=["logprobs", "exit_pdf"])


def param_shapes(config: Dict[str, Any]) -> dict:
    """The parameter tree, a ``jax.ShapeDtypeStruct`` for each leaf; a
    layer's leaves are stacked on a leading axis of ``num_hidden_layers``."""
    d, f, vocab = (config["hidden_size"], config["intermediate_size"],
                   config["vocab_size"])
    n, hd = config["num_hidden_layers"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    layers = {
        "q_proj": ((n, d, q), BF16), "k_proj": ((n, d, kv), BF16),
        "v_proj": ((n, d, kv), BF16), "o_proj": ((n, q, d), BF16),
        "gate": ((n, d, f), BF16), "up": ((n, d, f), BF16),
        "down": ((n, f, d), BF16),
        **{f"norm{i}": ((n, d), F32) for i in (1, 2, 3, 4)}}
    if config.get("tie_word_embeddings"):
        raise ValueError("tie_word_embeddings: the head is a leaf of its own")
    return lm_blocks.shape_tree({
        "embed": ((vocab, d), BF16), "layers": layers,
        "final_norm": ((d,), F32),
        "exit_gate": {"weight": ((d,), F32), "bias": ((1,), F32)},
        "head": ((d, vocab), BF16)})


def random_params(config: Dict[str, Any], seed: int = 0) -> dict:
    """Seeded stand-ins for trained weights, on the default device:
    matrices normal at ``1 / sqrt(fan_in)``, norm weights within 0.1 of 1,
    the gate's numbers of about unit spread."""
    d = config["hidden_size"]

    def special(leaf, k, shape, dtype):
        if leaf == "weight":  # the exit gate's: h has unit rms
            return jax.random.normal(k, shape, F32) / np.sqrt(d)
        if leaf == "bias":
            return jax.random.uniform(k, shape, F32, -0.5, 0.5)
        if dtype == F32:  # a norm's weight
            return jax.random.uniform(k, shape, F32, 0.9, 1.1)
        return None

    return lm_blocks.draw_tree(param_shapes(config), seed, special)


# -- what the loop did, into the registry -------------------------------------

def record_exit(exit_pdf_sum, rows: int,
                registry: Optional[Any] = None) -> None:
    """Sum a window of the model's ``exit_pdf`` output into the registry.
    ``exit_pdf_sum`` is the output summed over the window's ``rows`` rows
    (``[passes]``). Adds the rows to the counter ``loop.rows`` and sets
    the gauge ``loop.exit_step_mean`` to the pass at which the gate would
    let a position out on average (``sum of t x p_t``, ``t`` from 1).
    Every row takes every pass, so the layer applications are ``loop.rows``
    x ``total_ut_steps`` x ``num_hidden_layers`` and have no counter of
    their own."""
    from sparkdl_tpu.obs.registry import default_registry
    reg = registry or default_registry()
    reg.counter("loop.rows").add(int(rows))
    if rows:
        reg.gauge("loop.exit_step_mean").set(
            float(np.dot(np.arange(1, len(exit_pdf_sum) + 1), exit_pdf_sum) / rows))
