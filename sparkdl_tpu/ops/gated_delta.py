"""The gated delta rule of a linear-attention layer, computed in chunks.

Per head, with a state ``S`` of ``[dk, dv]`` that is zero before the
first position, the rule reads, position by position::

    S   <- exp(g_t) * S
    d_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

Run that way it is one tiny matrix-vector step per position: 8,192
dependent steps a row. :func:`gated_delta_rule` computes the same
numbers a chunk of ``C`` positions at a time (the WY form of Yang et
al., "Gated Delta Networks", 2024): inside a chunk the ``C`` rank-one
updates are folded into one ``C x C`` lower-triangular system, solved
for all of the sequence's chunks at once as dense batched matrix
products, and only the chunk-to-chunk recurrence, ``T / C`` steps of
a few ``[C, d]`` products each, stays sequential (a ``fori_loop``).
Sequences come heads first (``[B, H, T, d]``), so that cutting them
into chunks moves no data.

The gates, the decays, the triangular system and the state are
float32. The ``[C, d]`` products round their operands to the
parameters' type (bfloat16) and accumulate in float32, as every
matrix product of the model does;
the triangular system's own products run at ``Precision.HIGH``
(three bfloat16 passes), because its result multiplies everything a
chunk writes.

Everything here runs under the scope ``gdn_scan``, the name the
device trace's instructions keep (``obs/compile_log.py::
instruction_scopes``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: the scope every instruction of the rule carries in its ``op_name``
SCOPE = "gdn_scan"

_HIGHEST = lax.Precision.HIGHEST
#: the triangular system's products: three bfloat16 passes (an error of
#: about 2^-16 where one pass gives 2^-8), at half of HIGHEST's six
_EXACT = lax.Precision.HIGH


def _dot(a, b, spec: str, dtype):
    """An einsum with both operands rounded to ``dtype`` and a float32
    result: the same rounding on the CPU as on the chip."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=_HIGHEST if dtype == jnp.float32 else None)


def _unit_lower_inverse(m, chunk: int):
    """``(I - m)^-1`` for a strictly lower-triangular ``m`` of
    ``[..., C, C]``: ``m`` is nilpotent (``m^C = 0``), so the Neumann
    series ends, and it factors into ``log2(C)`` squarings:
    ``(I + m)(I + m^2)(I + m^4)...`` — matrix products the MXU takes,
    where forward substitution is ``C`` dependent row steps."""
    eye = jnp.eye(chunk, dtype=m.dtype)
    inv, power, reach = eye + m, m, 2
    while reach < chunk:
        power = jnp.matmul(power, power, precision=_EXACT)
        inv = jnp.matmul(inv, eye + power, precision=_EXACT)
        reach *= 2
    return inv


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     dtype=jnp.bfloat16):
    """``o`` of the rule above for whole sequences, heads first.

    ``q``, ``k``: ``[B, H, T, dk]`` (``k`` L2-normalised, ``q`` already
    scaled); ``v``: ``[B, H, T, dv]``; ``g`` (log-decay, at most 0) and
    ``beta``: ``[B, H, T]``. Returns float32 ``[B, H, T, dv]``. ``T`` need
    not be a multiple of ``chunk``: the tail is padded with positions
    that write nothing (``beta = 0``, ``g = 0``) and cut off again.
    ``dtype`` is what the ``[C, d]`` products round their operands to
    (the parameters' storage type; float32 makes them exact)."""
    with jax.named_scope(SCOPE):
        b, h, t, dk = q.shape
        pad = (-t) % chunk
        if pad:
            q, k, v, g, beta = (
                jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
                for x in (q, k, v, g, beta))
        n = (t + pad) // chunk

        def chunks(x):  # [B, H, T, ...] -> [B, H, n, C, ...]: no data moves
            return x.astype(jnp.float32).reshape((b, h, n, chunk) + x.shape[3:])

        q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
        gc = jnp.cumsum(g, axis=-1)  # decay from the chunk's start, own step included
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        # exp(gc_i - gc_j) for j <= i; the mask goes on the exponent so
        # that the upper half never overflows
        decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                                  -jnp.inf))
        k_beta = k * beta[..., None]
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        m = jnp.where(strict,
                      -jnp.einsum("...id,...jd->...ij", k_beta, k,
                                  precision=_EXACT) * decay, 0.0)
        solve = _unit_lower_inverse(m, chunk)
        u = jnp.matmul(solve, v * beta[..., None], precision=_EXACT)
        w = jnp.matmul(solve, k_beta * jnp.exp(gc)[..., None],
                       precision=_EXACT)
        qk = jnp.where(lower, _dot(q, k, "...id,...jd->...ij", dtype) * decay,
                       0.0)
        q_in = q * jnp.exp(gc)[..., None]
        g_last = gc[..., -1]
        k_out = k * jnp.exp(g_last[..., None] - gc)[..., None]
        carried = jnp.exp(g_last)

        def step(i, state_out):
            state, out = state_out
            u_i, w_i, qk_i, q_i, k_i, decay_i = (
                lax.dynamic_index_in_dim(x, i, axis=2, keepdims=False)
                for x in (u, w, qk, q_in, k_out, carried))
            v_new = u_i - _dot(w_i, state, "bhcd,bhde->bhce", dtype)
            o_i = (_dot(q_i, state, "bhcd,bhde->bhce", dtype)
                   + _dot(qk_i, v_new, "bhij,bhje->bhie", dtype))
            state = (state * decay_i[..., None, None]
                     + _dot(k_i, v_new, "bhcd,bhce->bhde", dtype))
            return state, lax.dynamic_update_index_in_dim(out, o_i, i, axis=2)

        state0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
        _, o = lax.fori_loop(0, n, step, (state0, jnp.zeros_like(u)))
        return o.reshape(b, h, t + pad, -1)[:, :, :t]
