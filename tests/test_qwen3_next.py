"""Qwen3-Next on the CPU at small widths: the program (``models/qwen3_next.py``
over ``ops/gated_delta.py``, ``ops/attention.py``, ``ops/moe.py``) against the
benchmark's plain reference (``benchmarks/reference/qwen3_next.py``), which
shares no code with it. float32 parameters make the program's products exact,
so the mathematics is held to 1e-4; bfloat16 parameters are the configuration
as it runs, held to what that rounding gives."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo_text
from benchmarks import lm_weights, model, program_lm
from benchmarks.comparers.logprob_rows import row_gaps
from benchmarks.reference import qwen3_next as reference
from benchmarks.reference.nn import Net
from sparkdl_tpu.models import qwen3_next
from sparkdl_tpu.ops import attention as attention_op
from sparkdl_tpu.ops import gated_delta, moe

SEED = 2**31 + 7


def small_config(**changes):
    """One period (3 delta-rule layers and a full one), 8 experts of which 3 are
    held, in the benchmark file's convention: ``num_experts`` counts the experts
    held, ``router_width`` the router's outputs."""
    config = dict(
        reference="qwen3_next", program={"module": "qwen3_next"}, head="logprobs",
        input_shape=[48], hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        partial_rotary_factor=0.25, rope_theta=1e7, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, num_experts=3, router_width=8, num_experts_per_tok=2,
        experts_held=[2, 5], moe_intermediate_size=32, shared_expert_intermediate_size=32,
        vocab_size=128, rms_norm_eps=1e-6,
        assumed={"A_log_range": [0.0, 1.7], "dt_bias_range": [-6.0, -2.0], "head_gain": 2.0})
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def small():
    config = small_config()
    weights = lm_weights.make_weights(config, SEED)
    tokens = lm_weights.token_rows(SEED, 4, 48, config["vocab_size"], 1.0)
    return config, weights, tokens


@pytest.mark.parametrize("dtype, limit", [("float32", 1e-4), ("bfloat16", 0.08)])
def test_program_matches_reference_on_logprobs(small, dtype, limit):
    config, weights, tokens = small
    ref = lm_weights.reference_outputs(config, weights, tokens)
    assert ref.shape == (4, 47) and ref.std(axis=1).min() > 1.0  # not flat
    if dtype == "float32":
        weights = {k: v.astype(jnp.float32) for k, v in weights.items()}
    mf = program_lm.model_function(config, weights, 48)
    assert mf.output_names == ["logprobs"]
    out = mf(tokens)
    assert out.dtype == jnp.float32 and out.shape == (4, 47)
    assert row_gaps(out, ref).max() < limit


@pytest.mark.parametrize("dtype, limit", [("float32", 1e-4), ("bfloat16", 0.05)])
def test_program_matches_reference_on_logits(small, dtype, limit):
    config, weights, tokens = small
    logits_config = dict(config, head="logits")
    ref = lm_weights.reference_outputs(logits_config, weights, tokens)
    assert ref.shape == (4, 47, 128)
    if dtype == "float32":
        weights = {k: v.astype(jnp.float32) for k, v in weights.items()}
    mf = program_lm.model_function(config, weights, 48)
    program_config = dict(config, num_experts=config["router_width"])
    hidden, _ = qwen3_next.final_hidden(mf.params, jnp.asarray(tokens), program_config)
    logits = jnp.dot(hidden[:, :-1].astype(jnp.float32),
                     mf.params["head"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gap = np.linalg.norm(np.asarray(logits) - ref) / np.linalg.norm(ref)
    assert gap < limit


def _delta_inputs(rng, b, t, h, dk, dv):
    q = rng.normal(size=(b, t, h, dk)).astype(np.float32)
    k = rng.normal(size=(b, t, h, dk)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * math.sqrt(dk)
    v = rng.normal(size=(b, t, h, dv)).astype(np.float32)
    g = -rng.uniform(0.001, 0.7, size=(b, t, h)).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, size=(b, t, h)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("length, chunk", [(64, 64), (128, 64), (50, 64), (97, 16), (1, 16)])
def test_chunked_delta_rule_matches_token_by_token(length, chunk):
    args = _delta_inputs(np.random.default_rng(length), 2, length, 3, 16, 24)
    step_by_step = reference.delta_rule_recurrence(*args)
    heads_first = tuple(jnp.swapaxes(a, 1, 2) for a in args)
    chunked = gated_delta.gated_delta_rule(*heads_first, chunk=chunk, dtype=jnp.float32)
    assert chunked.shape == (2, 3, length, 24)
    np.testing.assert_allclose(jnp.swapaxes(chunked, 1, 2), step_by_step, rtol=2e-4, atol=2e-5)


def test_chunked_delta_rule_in_bfloat16_stays_close():
    args = _delta_inputs(np.random.default_rng(3), 1, 128, 2, 16, 16)
    exact = np.asarray(reference.delta_rule_recurrence(*args))
    rounded = np.asarray(jnp.swapaxes(gated_delta.gated_delta_rule(
        *(jnp.swapaxes(a, 1, 2) for a in args), chunk=64), 1, 2))
    assert np.linalg.norm(rounded - exact) / np.linalg.norm(exact) < 0.02


def test_chunked_delta_rule_at_published_head_sizes_over_several_grid_steps():
    """dk = dv = 128, chunk 64: 600 positions are nine chunks and a ragged tail of 24, three
    grid steps of the kernel (two tiles of two chunks each), the state carried between them."""
    args = _delta_inputs(np.random.default_rng(600), 1, 600, 2, 128, 128)
    step_by_step = reference.delta_rule_recurrence(*args)
    chunked = gated_delta.gated_delta_rule(*(jnp.swapaxes(a, 1, 2) for a in args), dtype=jnp.float32)
    assert chunked.shape == (1, 2, 600, 128)
    np.testing.assert_allclose(jnp.swapaxes(chunked, 1, 2), step_by_step, rtol=2e-4, atol=2e-5)


def _hard_chunk(seed, t=128, h=2, d=128, spread=2.0):
    """Heads-first inputs whose keys lean on one direction within a chunk (mean cosine 0.2),
    beta 0.95 and a decay of 0.001 a step, and the rule's answer for them in float64 numpy.
    This is where ``I - m`` is worst conditioned and still solvable by its Neumann series: at
    a mean cosine of 0.5 the series' powers cancel so far that the parent's chain, in exact
    float32 on the CPU, is off by 1.9e3 of the answer's norm, and nothing can be held to it."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(1, h, 1, d)) + spread * rng.normal(size=(1, h, t, d))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(1, h, t, d))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * math.sqrt(d)
    v = rng.normal(size=(1, h, t, d))
    g, beta = np.full((1, h, t), -0.001), np.full((1, h, t), 0.95)
    exact = np.zeros_like(v)
    for hi in range(h):
        state = np.zeros((d, d))
        for ti in range(t):
            state = state * np.exp(g[0, hi, ti])
            state = state + np.outer(k[0, hi, ti], beta[0, hi, ti] * (v[0, hi, ti] - state.T @ k[0, hi, ti]))
            exact[0, hi, ti] = state.T @ q[0, hi, ti]
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)), exact


def test_delta_rule_kernel_keeps_three_passes_on_a_hard_chunk(monkeypatch):
    """The limit is the error of the parent's jnp chain (3d9c7a6, the triangular system at
    ``Precision.HIGH``) on this input on the chip: 4.86e-3 of the answer's norm (seeds 6 and 7:
    4.08e-3, 2.26e-3; `tools/chip_calls/pr29_kernel.py`, PR 29). The kernel reads 1.61e-3 there
    and 1.41e-3 here, in the interpreter; the parent's chain here, where ``HIGH`` is exact
    float32, 7.4e-5. With the system's products in one bfloat16 pass the kernel reads 3.6."""
    inputs, exact = _hard_chunk(5)

    def gap():
        out = np.asarray(gated_delta.gated_delta_rule(*inputs, dtype=jnp.float32), np.float64)
        return np.linalg.norm(out - exact) / np.linalg.norm(exact)

    assert gap() < 4.86e-3
    whole = gated_delta._split
    monkeypatch.setattr(gated_delta, "_split", lambda x: (whole(x)[0], jnp.zeros(x.shape, jnp.bfloat16)))
    assert gap() > 0.1


# key heads, value heads, positions, whether q, k and v are head ranges of one [q | k | v]
# array; at _HEADS = 4 value heads a grid step
_UNREPEATED_CASES = {
    "ratio 2, arrays of their own": (4, 8, 128, False),
    "ratio 4, arrays of their own, a ragged tail": (2, 8, 100, False),
    "ratio 8: two steps read one key head": (1, 8, 128, False),
    "ranges of one array, each at a whole block": (4, 8, 128, True),
    "ranges of one array, v off its block and cut out": (1, 4, 128, True),
}


@pytest.mark.parametrize("case", list(_UNREPEATED_CASES))
def test_unrepeated_keys_give_bit_for_bit_what_repeated_keys_give(case):
    """q and k repeated to the value heads (``jnp.repeat``, the published ``repeat_interleave``
    order) and passed as three arrays are the reference; the rule reads the key heads
    unrepeated, in place where they lie, and has to give the same bits."""
    hk, hv, t, one_array = _UNREPEATED_CASES[case]
    rng = np.random.default_rng(hk * hv + t)
    q, k, v, g, beta = (jnp.swapaxes(a, 1, 2) for a in _delta_inputs(rng, 1, t, hv, 16, 16))
    q, k = q[:, ::hv // hk], k[:, ::hv // hk]
    repeated = gated_delta.gated_delta_rule(
        jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1), v, g, beta,
        dtype=jnp.float32)
    if one_array:
        qkv = jnp.concatenate([q, k, v], axis=1)
        q, k, v = (gated_delta.Heads(qkv, 0, hk), gated_delta.Heads(qkv, hk, hk),
                   gated_delta.Heads(qkv, 2 * hk, hv))
    unrepeated = gated_delta.gated_delta_rule(q, k, v, g, beta, dtype=jnp.float32)
    assert unrepeated.shape == (1, hv, t, 16)
    np.testing.assert_array_equal(unrepeated, repeated)


@pytest.mark.parametrize("key_heads", [4, 2],
                         ids=["4 do not divide 6", "3 value heads a key head, 2 a step"])
def test_delta_rule_refuses_key_heads_that_the_value_heads_cannot_share_evenly(key_heads):
    rng = np.random.default_rng(0)
    q, k, v, g, beta = (jnp.swapaxes(a, 1, 2) for a in _delta_inputs(rng, 1, 64, 6, 16, 16))
    with pytest.raises(ValueError, match="evenly"):
        gated_delta.gated_delta_rule(q[:, :key_heads], k[:, :key_heads], v, g, beta)


def test_partial_rotary_leaves_the_last_three_quarters_untouched():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 3, 32)), jnp.float32)
    turned = qwen3_next.partial_rotary(x, 1e7, rotary_dim=8)
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(turned[:, 0], x[:, 0])  # position 0 turns by nothing
    assert not np.allclose(turned[:, 1:, :, :8], x[:, 1:, :, :8])
    # a rotation: the turned part keeps its length
    np.testing.assert_allclose(np.linalg.norm(turned[..., :8], axis=-1),
                               np.linalg.norm(x[..., :8], axis=-1), rtol=1e-5)
    np.testing.assert_allclose(turned, reference._rotary(x, 1e7, 8), rtol=1e-5, atol=1e-6)


# (positions, block, key heads, value width, rotary width or None, rotary key heads), then where
# a case is not at the first ones' key width of 8: (key width, the operands read in place)
_ATTENTION_CASES = {
    "grouped queries, ragged": (40, 16, 2, 8, None, 0),
    "one block": (32, 32, 2, 8, None, 0),
    "shorter than a block": (7, 16, 2, 8, None, 0),
    "values narrower than keys": (40, 16, 2, 4, None, 0),
    "values wider than keys, one key head": (33, 8, 1, 16, None, 0),
    "a rotary key that every head shares, ragged": (40, 16, 4, 8, 4, 1),
    "a rotary key, values narrower, one block": (32, 32, 4, 4, 4, 1),
    "a rotary key, shorter than a block": (7, 16, 4, 8, 6, 1),
    "a rotary key a group, several blocks": (70, 8, 4, 16, 4, 2),
    # at lane-tile widths the output is written tokens-first, and an operand may be read so
    "named in place at a width of 8: copied all the same": (40, 16, 2, 8, None, 0, 8, ("q", "k", "v")),
}
for _in_place in ((), ("q", "k", "v")):
    _how = "in place" if _in_place else "heads-first"
    _ATTENTION_CASES.update({
        f"heads of 128, grouped keys, {_how}": (48, 16, 2, 128, None, 0, 128, _in_place),
        f"heads of 256, two key heads, {_how}": (32, 16, 2, 256, None, 0, 256, _in_place),
        f"heads of 128 and a 64-wide rotary pair with one key head, {_how}": (32, 16, 4, 128, 64, 1, 128, _in_place),
        f"heads of 128, no multiple of the block, {_how}": (37, 16, 2, 128, None, 0, 128, _in_place),
        f"heads of 128, shorter than a block, values of 256, {_how}": (5, 16, 1, 256, None, 0, 128, _in_place),
    })
_ATTENTION_CASES["heads of 128, only the keys in place"] = (40, 16, 2, 128, None, 0, 128, ("k",))


def _dense_attention(q, k, v, rope, scale):
    length, heads = q.shape[1], q.shape[2]
    every_head = lambda x: jnp.repeat(x, heads // x.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, every_head(k))
    if rope:
        s = s + jnp.einsum("bqhd,bkhd->bhqk", rope[0], every_head(rope[1]))
    s = jnp.where(np.tril(np.ones((length, length), bool)), s * scale, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), every_head(v))


@pytest.mark.parametrize("case", list(_ATTENTION_CASES))
def test_blockwise_causal_attention_matches_a_dense_softmax(case):
    length, block, kv_heads, dv, dr, rope_heads, d, in_place = (_ATTENTION_CASES[case] + (8, ()))[:8]
    rng = np.random.default_rng(length + dv)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape) * min(1.0, (8 / shape[-1]) ** 0.5), jnp.float32)
    q, k, v = draw(2, length, 4, d), draw(2, length, kv_heads, d), draw(2, length, kv_heads, dv)
    rope = (draw(2, length, 4, dr), draw(2, length, rope_heads, dr)) if dr else None
    out = attention_op.causal_attention(q, k, v, 0.35, block=block, dtype=jnp.float32, rope=rope,
                                        in_place=in_place)
    assert out.shape == (2, length, 4, dv) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, _dense_attention(q, k, v, rope, 0.35), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("case", ["lane tiles, in place", "lane tiles, heads-first", "narrow: cut and copied",
                                  "keys of 256 in heads of 384: cut and copied, the values in place"])
def test_keys_and_values_are_read_out_of_one_array(case):
    """Latent attention's ``[k_nope | v]`` a head: the kernel takes both out of ``kv``."""
    d, dv = {"narrow: cut and copied": (8, 8),
             "keys of 256 in heads of 384: cut and copied, the values in place": (256, 128)}.get(case, (128, 128))
    in_place = () if case == "lane tiles, heads-first" else ("q", "k", "v")
    rng = np.random.default_rng(d)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape) * min(1.0, (8 / shape[-1]) ** 0.5), jnp.float32)
    q, kv = draw(2, 40, 4, d), draw(2, 40, 2, d + dv)
    out = attention_op.causal_attention(
        q, attention_op.HeadSlice(kv, 0, d), attention_op.HeadSlice(kv, d, dv), 0.35, block=16,
        dtype=jnp.float32, in_place=in_place)
    assert out.shape == (2, 40, 4, dv)
    np.testing.assert_allclose(out, _dense_attention(q, kv[..., :d], kv[..., d:], None, 0.35),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(out, attention_op.causal_attention(
        q, kv[..., :d], kv[..., d:], 0.35, block=16, dtype=jnp.float32))


@pytest.mark.parametrize("width", [8, 128])
def test_attention_rounds_its_output_as_a_cast_of_the_float32_output_would(width):
    """``out_dtype`` moves the rounding into the kernel's last write and nowhere else: the
    bits are those of the default's float32 output, cast."""
    rng = np.random.default_rng(width)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = draw(2, 40, 4, width), draw(2, 40, 2, width), draw(2, 40, 2, width)
    whole = attention_op.causal_attention(q, k, v, width ** -0.5, block=16)
    rounded = attention_op.causal_attention(q, k, v, width ** -0.5, block=16, out_dtype=jnp.bfloat16)
    assert whole.dtype == jnp.float32 and rounded.dtype == jnp.bfloat16
    np.testing.assert_array_equal(rounded, whole.astype(jnp.bfloat16))


def test_attention_refuses_an_operand_it_does_not_have():
    x = jnp.zeros((1, 8, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="in_place"):
        attention_op.causal_attention(x, x, x, 1.0, in_place=("q", "o"))


def _moe_params(rng, d, f, experts):
    leaf = lambda *shape: jnp.asarray(rng.normal(size=shape) / math.sqrt(shape[-2]), jnp.float32)
    return {"router": leaf(d, experts), "experts_gate": leaf(experts, d, f),
            "experts_up": leaf(experts, d, f), "experts_down": leaf(experts, f, d),
            "shared_gate": leaf(d, f), "shared_up": leaf(d, f), "shared_down": leaf(f, d),
            "shared_router": jnp.asarray(rng.normal(size=(d,)) / math.sqrt(d), jnp.float32)}


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """Shares [0,2) [2,4) [4,6) [6,8) of 8 experts, each computed by the program
    with its own slice of the matrices, the shared expert counted once, against
    the reference's layer with all 8."""
    rng = np.random.default_rng(11)
    d, f, experts = 32, 16, 8
    whole = _moe_params(rng, d, f, experts)
    x = jnp.asarray(rng.normal(size=(2, 24, d)), jnp.float32)
    base = small_config(hidden_size=d, moe_intermediate_size=f,
                        shared_expert_intermediate_size=f, num_experts_per_tok=3)
    uncut = dict(base, num_experts=experts, router_width=experts, experts_held=[0, experts])
    flat = {f"SparseMoe_0/{k}": v for k, v in whole.items()}
    expected, counts = reference.sparse_moe(Net(params=flat), x, uncut)
    assert counts.shape == (2, experts) and int(counts.sum()) == 2 * 24 * 3
    total = jnp.zeros_like(x)
    for first in range(0, experts, 2):
        share = dict(whole)
        for name in ("experts_gate", "experts_up", "experts_down"):
            share[name] = whole[name][first:first + 2]
        if first:  # what every chip computes alike is counted once
            share["shared_down"] = jnp.zeros_like(whole["shared_down"])
        config = dict(base, num_experts=experts, experts_held=[first, first + 2])
        y, chosen = qwen3_next.sparse_moe(share, x, config)
        assert chosen.shape == (48, 3)
        total = total + y
    np.testing.assert_allclose(total, expected, rtol=1e-4, atol=1e-5)


def _expert_by_expert(x, experts, weights, w_gate, w_up, w_down, first):
    out = np.zeros(x.shape, np.float64)
    for n in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[n, j]) - first
            if 0 <= e < w_gate.shape[0]:
                hidden = jax.nn.silu(x[n] @ w_gate[e]) * (x[n] @ w_up[e])
                out[n] += float(weights[n, j]) * np.asarray(hidden @ w_down[e])
    return out


@pytest.mark.parametrize("tile", [8, 16])
def test_held_experts_ffn_matches_expert_by_expert(tile):
    rng = np.random.default_rng(tile)
    p = _moe_params(rng, 32, 16, 3)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    experts, weights = moe.route(jnp.asarray(rng.normal(size=(40, 8)), jnp.float32), 3)
    np.testing.assert_allclose(weights.sum(axis=-1), 1.0, rtol=1e-6)
    y, counts = moe.held_experts_ffn(x, experts, weights, p["experts_gate"], p["experts_up"],
                                     p["experts_down"], first=2, tile=tile)
    assert counts.tolist() == [int((np.asarray(experts) == e).sum()) for e in (2, 3, 4)]
    expected = _expert_by_expert(x, experts, weights, p["experts_gate"], p["experts_up"],
                                 p["experts_down"], 2)
    np.testing.assert_allclose(y, expected, rtol=1e-4, atol=1e-5)


def test_no_token_is_dropped_when_every_token_picks_the_same_expert():
    """All 40 tokens on held expert 3 (and on two experts held elsewhere): its group
    is 40 rows where an even share would be 5; every one is computed."""
    rng = np.random.default_rng(5)
    p = _moe_params(rng, 32, 16, 3)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    experts = jnp.tile(jnp.asarray([[3, 0, 7]], jnp.int32), (40, 1))
    weights = jnp.tile(jnp.asarray([[0.5, 0.3, 0.2]], jnp.float32), (40, 1))
    y, counts = moe.held_experts_ffn(x, experts, weights, p["experts_gate"], p["experts_up"],
                                     p["experts_down"], first=2, tile=8)
    assert counts.tolist() == [0, 40, 0]
    expected = _expert_by_expert(x, experts, weights, p["experts_gate"], p["experts_up"],
                                 p["experts_down"], 2)
    assert np.abs(expected).min(axis=1).max() > 0  # every token has an answer to match
    np.testing.assert_allclose(y, expected, rtol=1e-4, atol=1e-5)
    # and when nothing at all falls on the experts held, the answer is zero
    nowhere = jnp.tile(jnp.asarray([[0, 1, 7]], jnp.int32), (40, 1))
    y, counts = moe.held_experts_ffn(x, nowhere, weights, p["experts_gate"], p["experts_up"],
                                     p["experts_down"], first=2, tile=8)
    assert counts.tolist() == [0, 0, 0] and not np.asarray(y).any()


def _grouped_inputs(rng, n, d, f, tile, dtype):
    p = {name: v.astype(dtype) for name, v in _moe_params(rng, d, f, 3).items()
         if name.startswith("experts_")}
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    experts = jnp.asarray(np.argsort(rng.random((n, 8)), axis=1)[:, :3], jnp.int32)
    weights = jnp.asarray(rng.dirichlet(np.ones(3), size=n), jnp.float32)
    row_token, dest, is_held, tile_expert, tiles_used, _ = moe.grouped_layout(
        experts, first=2, held=3, tile=tile)
    return p, x, experts, weights, row_token, tile_expert, tiles_used, dest, is_held


def _gathered(x, row_token):
    """The parent's way in (d1cff70): XLA gathers every row of the grouped buffer, a zero row
    where the row is padding. ``(x_rows, arange)``: the kernel then takes row ``r`` from
    ``x_rows[r]``."""
    x_rows = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[row_token]
    return x_rows, jnp.arange(len(row_token), dtype=jnp.int32)


@pytest.mark.parametrize("blocks, dtype", [(2, jnp.float32), (4, jnp.float32), (4, jnp.bfloat16)])
def test_expert_kernel_walks_an_expert_too_wide_for_vmem_in_blocks_of_its_width(
        blocks, dtype, monkeypatch):
    """An expert of 256 x 512 under a VMEM budget that two copies of 1 / ``blocks`` of its
    matrices fill: the grid gains a second axis, the down-product is carried across it, and
    the rows are expert by expert what one block gives."""
    d, f, tile, size = 256, 512, 16, jnp.dtype(dtype).itemsize
    rng = np.random.default_rng(blocks)
    p, x, experts, weights, row_token, tile_expert, tiles_used, dest, is_held = _grouped_inputs(
        rng, 50, d, f, tile, dtype)
    run = lambda: moe.grouped_swiglu(x.astype(dtype), row_token, tile_expert, tiles_used,
                                     p["experts_gate"], p["experts_up"], p["experts_down"], tile)
    assert moe.width_block(d, f, size) == f and moe.row_tile(d, f, size) == 128
    whole = run()
    monkeypatch.setattr(moe, "_WEIGHTS_VMEM", 2 * 3 * d * (f // blocks) * size)
    assert moe.width_block(d, f, size) == f // blocks and moe.row_tile(d, f, size) == 256
    jax.clear_caches()  # the kernel is jitted: what it traced read the old budget
    assert f"({-(-len(row_token) // tile)}, {blocks})" in str(jax.make_jaxpr(run)())
    blocked = run()
    assert blocked.shape == whole.shape and blocked.dtype == whole.dtype
    used = int(tiles_used) * tile
    if dtype == jnp.float32:
        np.testing.assert_allclose(_rows_of(blocked)[:used], _rows_of(whole)[:used],
                                   rtol=1e-5, atol=1e-6)
        y = np.asarray(moe.combine_held(blocked, dest, is_held, weights))
        expected = _expert_by_expert(x, experts, weights, p["experts_gate"], p["experts_up"],
                                     p["experts_down"], 2)
        np.testing.assert_allclose(y, expected, rtol=1e-4, atol=1e-5)
    else:  # the sum over the blocks is float32 and rounded once, as the whole product is
        np.testing.assert_allclose(_rows_of(blocked)[:used], _rows_of(whole)[:used],
                                   rtol=2 ** -7, atol=1e-3)
    monkeypatch.setattr(moe, "_WEIGHTS_VMEM", 2 * 3 * d * 64 * size)
    with pytest.raises(ValueError):  # no multiple of 128 columns fits
        moe.width_block(d, f, size)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_kernel_at_one_block_gives_bit_for_bit_what_the_plain_products_give(dtype):
    """Where an expert fits VMEM whole (every shape of PR 31) nothing is carried: each tile's
    slab is the three products of the kernel before the second grid axis, bit for bit."""
    d, f, tile = 64, 32, 8
    p, x, _, _, row_token, tile_expert, tiles_used, _, _ = _grouped_inputs(
        np.random.default_rng(1), 40, d, f, tile, dtype)
    y_rows = moe.grouped_swiglu(x.astype(dtype), row_token, tile_expert, tiles_used,
                                p["experts_gate"], p["experts_up"], p["experts_down"], tile)
    x_rows, _ = _gathered(x.astype(dtype), row_token)
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    dot = lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32, precision=precision)
    for t in range(int(tiles_used)):
        e, rows = int(tile_expert[t]), x_rows[t * tile:(t + 1) * tile]
        gate, up = dot(rows, p["experts_gate"][e]), dot(rows, p["experts_up"][e])
        y = dot((gate * jax.nn.sigmoid(gate) * up).astype(dtype), p["experts_down"][e])
        if dtype == jnp.bfloat16:
            y = y.astype(jnp.bfloat16).astype(jnp.float32)
        real = np.asarray(row_token[t * tile:(t + 1) * tile]) < len(x)  # not padding
        assert real.any()
        np.testing.assert_array_equal(_rows_of(y_rows)[t * tile:(t + 1) * tile][real],
                                      np.asarray(y)[real])


def _rows_of(y_rows):
    """The grouped result's rows as ``[R, D]`` float32, out of their slabs of words."""
    y_rows = np.asarray(y_rows)
    if y_rows.dtype == np.float32:
        return y_rows.reshape(len(y_rows), -1)
    halves = np.stack([y_rows << 16, y_rows & np.uint32(0xFFFF0000)], axis=2)
    return halves.view(np.float32).reshape(len(y_rows), -1)


_HELD, _ELSEWHERE = (2, 3, 4), (0, 1, 7)
_COMBINE_CASES = {
    # name: (tokens, tile, dtype, NaN in the rows nobody holds, the experts of token n)
    "nothing held": (40, 8, jnp.float32, False, lambda rng, n: _ELSEWHERE),
    "everything held": (40, 8, jnp.float32, False, lambda rng, n: rng.permutation(_HELD)),
    "all tokens on one held expert": (40, 8, jnp.float32, False, lambda rng, n: (3, 0, 7)),
    "a token with every choice held beside one with none": (
        40, 8, jnp.float32, False, lambda rng, n: _ELSEWHERE if n % 2 else _HELD),
    "40 tokens, tile 8": (40, 8, jnp.float32, False, None),
    "40 tokens, tile 16": (40, 16, jnp.float32, False, None),
    "129 tokens, tile 8": (129, 8, jnp.float32, False, None),
    "129 tokens, tile 16": (129, 16, jnp.float32, False, None),
    "NaN in every row nobody holds": (129, 8, jnp.float32, True, None),
    "bfloat16 rows, two to a word": (129, 16, jnp.bfloat16, False, None),
    "bfloat16 rows, NaN in every row nobody holds": (40, 8, jnp.bfloat16, True, None),
}


@pytest.mark.parametrize("case", list(_COMBINE_CASES))
def test_combine_kernel_sums_the_held_rows_and_reads_no_other(case):
    """`moe_combine` on the expert kernel's own rows, against every expert applied to every
    token that chose it (float32) or the gather and masked sum it replaced (bfloat16)."""
    n, tile, dtype, poison, rule = _COMBINE_CASES[case]
    rng = np.random.default_rng(len(case))
    p = {name: v.astype(dtype) for name, v in _moe_params(rng, 64, 16, 3).items()
         if name.startswith("experts_")}
    x = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
    if rule is None:
        experts = np.argsort(rng.random((n, 8)), axis=1)[:, :3]
    else:
        experts = np.asarray([rule(rng, i) for i in range(n)])
    experts = jnp.asarray(experts, jnp.int32)
    weights = jnp.asarray(rng.dirichlet(np.ones(3), size=n), jnp.float32)
    row_token, dest, is_held, tile_expert, tiles_used, _ = moe.grouped_layout(
        experts, first=2, held=3, tile=tile)
    y_rows = moe.grouped_swiglu(x.astype(dtype), row_token, tile_expert, tiles_used,
                                p["experts_gate"], p["experts_up"], p["experts_down"], tile)
    assert y_rows.shape == (moe.layout_rows(3 * n, 3, tile), *moe.slab_shape(64, dtype))
    rows = _rows_of(y_rows)
    taken = np.asarray(dest)[np.asarray(is_held)]
    if poison:  # padding rows, rows of tiles past `tiles_used`: never looked at
        nobody = np.setdiff1d(np.arange(len(rows)), taken)
        assert len(nobody) >= tile
        filler = jnp.nan if dtype == jnp.float32 else jnp.uint32(0x7FC07FC0)
        y_rows = y_rows.at[nobody].set(filler)
        assert np.isnan(_rows_of(y_rows)[nobody]).all()
    y = np.asarray(moe.combine_held(y_rows, dest, is_held, weights))
    assert y.shape == (n, 64) and np.isfinite(y).all()
    if dtype == jnp.float32:
        expected = _expert_by_expert(x, experts, weights, p["experts_gate"], p["experts_up"],
                                     p["experts_down"], 2)
    else:
        picked = rows[np.where(is_held, dest, 0)] * np.asarray(weights)[..., None]
        expected = np.where(np.asarray(is_held)[..., None], picked, 0.0).sum(axis=1)
    np.testing.assert_allclose(y, expected, rtol=1e-4, atol=1e-5)
    if case == "nothing held":
        assert not y.any() and not len(taken)
    else:
        assert np.abs(expected).max() > 0.01


_FETCH_CASES = {
    # name: (tokens, tile, dtype, blocks of the width, NaN in the rows of `x` that no held
    #        assignment names, the experts of token n)
    "nothing held": (40, 8, jnp.float32, 1, True, lambda rng, n: _ELSEWHERE),
    "everything held": (40, 8, jnp.float32, 1, False, lambda rng, n: rng.permutation(_HELD)),
    "one expert takes every token": (40, 8, jnp.float32, 1, False, lambda rng, n: (3, 0, 7)),
    "a token with every choice held beside one with none": (
        40, 8, jnp.bfloat16, 1, True, lambda rng, n: _ELSEWHERE if n % 2 else _HELD),
    "40 tokens, tile 8": (40, 8, jnp.float32, 1, False, None),
    "40 tokens, tile 16": (40, 16, jnp.float32, 1, False, None),
    "129 tokens, tile 8": (129, 8, jnp.float32, 1, False, None),
    "129 tokens, tile 16": (129, 16, jnp.float32, 1, False, None),
    "bfloat16 rows, two to a word, tile 8": (40, 8, jnp.bfloat16, 1, False, None),
    "bfloat16 rows, two to a word, tile 16": (129, 16, jnp.bfloat16, 1, False, None),
    "two blocks of the width": (129, 8, jnp.float32, 2, False, None),
    "bfloat16 rows, two blocks of the width": (40, 16, jnp.bfloat16, 2, False, None),
    "NaN in every row of x that nobody holds": (129, 8, jnp.float32, 1, True, None),
    "NaN in every row of x that nobody holds, tile 16": (40, 16, jnp.float32, 1, True, None),
    "bfloat16 rows, NaN in every row of x that nobody holds": (129, 16, jnp.bfloat16, 1, True, None),
    "two blocks of the width, NaN in every row of x that nobody holds": (
        40, 8, jnp.bfloat16, 2, True, None),
}


@pytest.mark.parametrize("case", list(_FETCH_CASES))
def test_expert_kernel_fetches_the_rows_in_use_and_reads_no_other(case, monkeypatch):
    """`held_experts_ffn`, whose kernel takes a tile's rows from `x` by one copy a row, against
    the parent's way in (XLA's gather of every row of the grouped buffer, then the same
    kernel's products and the same combine): bit for bit. A row of `x` that no held assignment
    names is never part of an answer: NaN there leaves every answer finite, and zero for
    that token."""
    n, tile, dtype, blocks, poison, rule = _FETCH_CASES[case]
    d, f = 64, 256
    rng = np.random.default_rng(len(case))
    p = {name: v.astype(dtype) for name, v in _moe_params(rng, d, f, 3).items()
         if name.startswith("experts_")}
    matrices = (p["experts_gate"], p["experts_up"], p["experts_down"])
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    if rule is None:
        experts = np.argsort(rng.random((n, 8)), axis=1)[:, :3]
    else:
        experts = np.asarray([rule(rng, i) for i in range(n)])
    experts = jnp.asarray(experts, jnp.int32)
    weights = jnp.asarray(rng.dirichlet(np.ones(3), size=n), jnp.float32)
    if blocks > 1:
        monkeypatch.setattr(moe, "_WEIGHTS_VMEM",
                            2 * 3 * d * (f // blocks) * jnp.dtype(dtype).itemsize)
        jax.clear_caches()  # the kernel is jitted: what it traced read the old budget
    assert moe.width_block(d, f, jnp.dtype(dtype).itemsize) == f // blocks
    row_token, dest, is_held, tile_expert, tiles_used, _ = moe.grouped_layout(
        experts, first=2, held=3, tile=tile)
    expected = np.asarray(moe.combine_held(
        moe.grouped_swiglu(*_gathered(x.astype(dtype), row_token), tile_expert, tiles_used,
                           *matrices, tile),
        dest, is_held, weights))
    named = np.asarray(is_held).any(axis=1)
    if poison:
        assert not named.all()
        x = jnp.where(named[:, None], x, jnp.nan)
    y, _ = moe.held_experts_ffn(x, experts, weights, *matrices, first=2, tile=tile)
    y = np.asarray(y)
    if blocks > 1:
        jax.clear_caches()  # nothing traced under this budget outlives the test
    assert y.shape == (n, d) and np.isfinite(y).all()
    np.testing.assert_array_equal(y, expected)
    assert not y[~named].any()
    if case == "nothing held":
        assert not named.any() and int(tiles_used) == 0
    else:
        assert np.abs(y[named]).max(axis=1).min() > 1e-3  # every named token has an answer
        if dtype == jnp.float32:  # and the answer is the layer's
            np.testing.assert_allclose(
                y, _expert_by_expert(np.nan_to_num(x), experts, weights, *matrices, 2),
                rtol=1e-4, atol=1e-5)


def test_grouped_layout_starts_every_group_on_a_tile():
    experts = jnp.asarray(np.random.default_rng(2).integers(0, 8, size=(30, 2)), jnp.int32)
    row_token, dest, is_held, tile_expert, tiles_used, counts = moe.grouped_layout(
        experts, first=2, held=3, tile=8)
    rows = moe.layout_rows(60, 3, 8)
    assert row_token.shape == (rows,) and tile_expert.shape == (rows // 8,)
    assert int(tiles_used) == int(sum(-(-int(c) // 8) for c in counts))
    dest, is_held, row_token = np.asarray(dest), np.asarray(is_held), np.asarray(row_token)
    assert is_held.sum() == int(counts.sum())
    # each held assignment has a row of its own, that row holds its token, and the
    # row's tile belongs to its expert
    taken = dest[is_held]
    assert len(set(taken.tolist())) == len(taken)
    tokens = np.repeat(np.arange(30), 2).reshape(30, 2)
    assert (row_token[taken] == tokens[is_held]).all()
    assert (np.asarray(tile_expert)[taken // 8] == np.asarray(experts)[is_held] - 2).all()
    assert (row_token == 30).sum() == rows - len(taken)  # the rest is padding


def _layout_by_sort(experts, first, held, tile):
    """`ops/moe.py::grouped_layout` as it was until PR 37, a stable sort by expert, two
    lookups in tables by expert and two index scatters: the reference the counting one
    is held to."""
    n, k = experts.shape
    a = n * k
    rows = moe.layout_rows(a, held, tile)
    local = experts.reshape(a) - first
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32), axis=0,
                     dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    padded = -(-counts // tile) * tile
    pad_ends = jnp.cumsum(padded)
    pad_starts = pad_ends - padded
    key_sorted, order = jax.lax.sort((key, jnp.arange(a, dtype=jnp.int32)),
                                     num_keys=1)
    group = jnp.minimum(key_sorted, held - 1)
    rank = jnp.arange(a, dtype=jnp.int32) - starts[group]
    dest_sorted = jnp.where(key_sorted < held, pad_starts[group] + rank, rows)
    row_token = jnp.full((rows,), n, jnp.int32).at[dest_sorted].set(
        order // k, mode="drop", unique_indices=True)
    dest = jnp.zeros((a,), jnp.int32).at[order].set(
        dest_sorted, unique_indices=True)
    tiles_used = pad_ends[-1] // tile
    tile_row = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    tile_expert = jnp.sum(pad_ends[None, :] <= tile_row[:, None], axis=1,
                          dtype=jnp.int32)
    last_used = jnp.maximum(tiles_used - 1, 0)
    tile_expert = jnp.minimum(tile_expert, held - 1)[
        jnp.minimum(jnp.arange(rows // tile), last_used)]
    return (row_token, dest.reshape(n, k), is_held.reshape(n, k),
            tile_expert, tiles_used.astype(jnp.int32), counts)


def _zipf_choices(seed, n, k, width):
    """``k`` distinct experts of ``width`` a token, expert ``e`` drawn with weight
    ``1 / (e + 1)``."""
    rng = np.random.default_rng(seed)
    scores = -np.log(np.arange(1, width + 1)) + rng.gumbel(size=(n, width))
    return np.argsort(-scores, axis=1)[:, :k].astype(np.int32)


def _whole_tiles(n, k, width, first, held, tile):
    """Choices under which every held expert's count is a multiple of ``tile``: token
    ``t``'s ``j``-th choice is expert ``(t // tile + j) % width``."""
    del first, held
    return ((np.arange(n)[:, None] // tile + np.arange(k)[None, :]) % width).astype(np.int32)


LAYOUT_CASES = {  # tokens, choices a token, the router's width, first, held, tile, the choices
    "cell 4's": (3000, 10, 512, 0, 128, 128, None),
    "cell 5's": (4000, 8, 192, 0, 12, 256, None),
    "a range that starts past expert 0": (1500, 10, 512, 128, 128, 128, None),
    "nothing held": (700, 4, 64, 0, 8, 16,
                     lambda n, k, width, first, held, tile:
                     held + _zipf_choices(3, n, k, width - held)),
    "everything held": (700, 4, 24, 0, 24, 16, None),
    "one expert takes every assignment": (
        700, 1, 16, 4, 8, 16,
        lambda n, k, width, first, held, tile: np.full((n, k), first + 5, np.int32)),
    "one assignment past a block": (257, 1, 16, 0, 8, 8, None),
    "a last block of one row's choices": (moe._COUNT_BLOCK + 1, 3, 16, 2, 5, 8, None),
    "fewer assignments than a block": (13, 2, 16, 0, 4, 8, None),
    "counts that are whole tiles": (512, 4, 16, 4, 8, 32, _whole_tiles),
}


@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_grouped_layout_by_counting_equals_the_one_by_sorting(case):
    """A stable sort ranks an expert's assignments in the order they come, and so does a
    count of the earlier ones: the same integers, so the three kernels see what they saw."""
    n, k, width, first, held, tile, choices = LAYOUT_CASES[case]
    experts = jnp.asarray(choices(n, k, width, first, held, tile) if choices
                          else _zipf_choices(len(case), n, k, width))
    got = jax.jit(lambda e: moe.grouped_layout(e, first, held, tile))(experts)
    expected = jax.jit(lambda e: _layout_by_sort(e, first, held, tile))(experts)
    names = ("row_token", "dest", "is_held", "tile_expert", "tiles_used", "counts")
    is_held = np.asarray(expected[2])
    for name, ours, theirs in zip(names, got, expected):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype, name
        ours, theirs = np.asarray(ours), np.asarray(theirs)
        if name == "dest":  # where nothing is held the row is never read
            ours, theirs = ours[is_held], theirs[is_held]
        np.testing.assert_array_equal(ours, theirs, err_msg=name)
    if case == "counts that are whole tiles":
        counts = np.asarray(got[5])
        assert counts.min() > 0 and not (counts % tile).any()
        assert int(got[4]) * tile == counts.sum()  # no padding row in any group
    if case == "nothing held":
        assert not is_held.any() and int(got[4]) == 0
    if case == "everything held":
        assert is_held.all()


def test_routing_output_counts_what_the_router_chose(small):
    config, weights, tokens = small
    mf = program_lm.model_function(config, weights, 48, routing_stats=True)
    assert mf.output_names == ["logprobs", "routing"]
    out = mf({"tokens": tokens})
    routing = np.asarray(out["routing"])
    assert routing.shape == (4, 4, 1 + 3) and routing.dtype == np.int32
    np.testing.assert_array_equal(routing[..., 0], routing[..., 1:].sum(axis=-1))
    assert 0 < routing[..., 0].max() <= 48 * 2
    plain = program_lm.model_function(config, weights, 48)(tokens)
    np.testing.assert_allclose(out["logprobs"], plain, rtol=1e-5, atol=1e-6)

    from sparkdl_tpu.obs.registry import MetricsRegistry
    registry = MetricsRegistry()
    qwen3_next.record_routing(routing, assignments=4 * 48 * 2 * 4, registry=registry)
    seen = registry.snapshot()
    assert seen["moe.assignments"] == 4 * 48 * 2 * 4
    assert seen["moe.assignments_held"] == routing[..., 0].sum()
    assert seen["moe.expert_load_max"] == routing.sum(axis=0)[:, 1:].max()


def test_through_tensor_transformer_across_a_partition_boundary(small, loaded_ahead):
    """An int32 token column through ``TensorTransformer``: the rows the direct call
    gives, with the second partition's first batch launched under the first's last."""
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.data.tensors import arrow_to_tensor
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer
    import pyarrow as pa
    from benchmarks.drivers.stream import _partitions

    config, weights, _ = small
    tokens = lm_weights.token_rows(SEED + 1, 10, 48, config["vocab_size"], 1.0)
    mf = program_lm.model_function(config, weights, 48)
    parts = _partitions(tokens, 5, 2, 5, "tokens")
    assert parts[0].schema.field("tokens").type.value_type == pa.int32()
    t = TensorTransformer(modelFunction=mf, inputMapping={"tokens": "tokens"},
                          outputMapping={"logprobs": "logprobs"}, batchSize=2)
    out = t.transform(DataFrame.from_batches(parts)).collect()
    scores = arrow_to_tensor(out.column("logprobs"))
    assert scores.shape == (10, 47) and scores.dtype == np.float32
    direct = np.concatenate([np.asarray(mf(tokens[lo:lo + 2]))
                             for lo in range(0, 10, 2)])
    np.testing.assert_allclose(scores, direct, rtol=1e-4, atol=1e-4)
    assert t.metrics.boundary_carried == 1 and t.metrics.boundary_cold == 0


def test_random_params_fill_the_tree_the_builder_describes():
    config = dict(small_config(), num_experts=8)  # the program's own convention
    shapes = qwen3_next.param_shapes(config)
    params = qwen3_next.random_params(config, seed=3)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(shapes)
    for leaf, spec in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(shapes)):
        assert leaf.shape == spec.shape and leaf.dtype == spec.dtype
    assert params["layer_0"]["moe"]["experts_gate"].shape == (3, 64, 32)
    assert "q_proj" in params["layer_3"]["mixer"] and "A_log" in params["layer_0"]["mixer"]
    mf = qwen3_next.model_function(config, params, seq_len=20)
    rows = np.random.default_rng(0).integers(0, 128, size=(3, 20)).astype(np.int32)
    scores = np.asarray(mf(rows))
    assert scores.shape == (3, 19) and np.isfinite(scores).all() and (scores < 0).all()
    # causal: a row's early scores do not depend on its later tokens
    changed = rows.copy()
    changed[:, 12:] = (changed[:, 12:] + 1) % 128
    again = np.asarray(mf(changed))
    np.testing.assert_allclose(again[:, :11], scores[:, :11], rtol=1e-4, atol=1e-5)
    assert not np.allclose(again[:, 12:], scores[:, 12:])


# -- the kernel at the configuration's widths, compiled for the chip without it ------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_expert_kernel_compiles_for_the_chip_at_published_widths(one_chip, monkeypatch):
    monkeypatch.setattr(moe, "_use_interpreter", lambda: False)
    n, held, d, f, tile = 16384, 128, 2048, 512, 128
    rows = moe.layout_rows(n * 10, held, tile)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = jax.jit(lambda *a: moe.grouped_swiglu(*a, tile=tile))
    compiled = fn.lower(
        spec((n, d), jnp.float32), spec((rows,), jnp.int32), spec((rows // tile,), jnp.int32),
        spec((), jnp.int32), spec((held, d, f), jnp.bfloat16), spec((held, d, f), jnp.bfloat16),
        spec((held, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%moe_experts" in text and "%moe_slabs" in text


def test_expert_block_compiles_for_the_chip_with_no_copy_of_every_choice(one_chip, monkeypatch):
    monkeypatch.setattr(moe, "_use_interpreter", lambda: False)
    n, k, held, d, f = 16384, 10, 128, 2048, 512
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = jax.jit(lambda *a: moe.held_experts_ffn(*a, first=0))
    compiled = fn.lower(
        spec((n, d), jnp.float32), spec((n, k), jnp.int32), spec((n, k), jnp.float32),
        spec((held, d, f), jnp.bfloat16), spec((held, d, f), jnp.bfloat16),
        spec((held, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for name in ("%moe_slabs", "%moe_experts", "%moe_combine"):
        assert any(name in line and "/moe_experts/" in line for line in calls), name
    # one grouped buffer, the kernel's result ([180,224, 8, 128] words): no grouped copy of
    # the tokens' rows (the parent's gather, d1cff70, wrote a bfloat16 [180,224, 2,048]) and
    # nothing of the size of every token's ten choices (e9da041)
    rows = moe.layout_rows(n * k, held, moe.row_tile(d, f, 2))
    assert f"[{rows},{d}]" not in text
    # the parent (d1cff70) compiles to 1,478,157,312 bytes of temporaries at these shapes,
    # the row copies to 739,390,976; the limit lies halfway
    assert compiled.memory_analysis().temp_size_in_bytes < 1_108_774_144


@pytest.mark.parametrize("n, k, held, d, f", [(16384, 10, 128, 2048, 512),
                                              (16384, 8, 12, 7168, 2048)],
                         ids=["cell 4's shapes", "cell 5's shapes"])
def test_expert_block_compiles_for_the_chip_with_no_sort_and_one_scatter(
        n, k, held, d, f, one_chip, monkeypatch):
    """What the layout by sorting compiled to (e0f2849, cell 4's shapes: `sort` over the
    163,840 assignments, two gathers of as many lookups, two scatters) is not there: the
    counting one leaves a product, and one scatter of the tokens to their rows."""
    monkeypatch.setattr(moe, "_use_interpreter", lambda: False)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = jax.jit(lambda *a: moe.held_experts_ffn(*a, first=0))
    text = fn.lower(
        spec((n, d), jnp.float32), spec((n, k), jnp.int32), spec((n, k), jnp.float32),
        spec((held, d, f), jnp.bfloat16), spec((held, d, f), jnp.bfloat16),
        spec((held, f, d), jnp.bfloat16)).compile().as_text()
    entry = text[text.index("\nENTRY "):].splitlines()
    named = lambda op: [line for line in entry if f'/moe_experts/{op}"' in line]
    assert " sort(" not in text and not named("sort")
    assert len(named("scatter")) == 1
    # the gather left picks each tile's expert: as many lookups as the buffer has tiles
    tile = moe.row_tile(d, f, 2)
    tiles = moe.layout_rows(n * k, held, tile) // tile
    for line in named("gather"):
        assert f"[{n * k}]" not in line and f"[{n},{k}]" not in line, line
        assert "kind=kCustom" not in line or f" s32[{tiles}]" in line, line
    # the count itself: the ones' product with the triangle, the rows summed out of it in
    # the same fusion (no [assignments, held] array is written)
    product, = [line for line in entry if '/moe_experts/bej,ji->bei/dot_general"' in line]
    assert _hlo_text.is_product_fusion(text, product)
    assert f" f32[{-(-n * k // moe._COUNT_BLOCK)},{moe._COUNT_BLOCK}]" in product


def test_delta_rule_kernel_compiles_for_the_chip_at_published_widths(one_chip, monkeypatch):
    monkeypatch.setattr(gated_delta, "_use_interpreter", lambda: False)
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(gated_delta.gated_delta_rule).lower(
        spec((2, 16, 8192, 128)), spec((2, 16, 8192, 128)), spec((2, 32, 8192, 128)),
        spec((2, 32, 8192)), spec((2, 32, 8192))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%gdn_scan" in text
    # the chunks' systems never reach the chip's memory: the parent's jnp chain (3d9c7a6) compiles
    # to 952,679,936 bytes of temporaries at these shapes (its [2, 32, 128, 64, 64] float32
    # tensors are 134 MB each), the kernel to 0; the limit lies halfway
    assert compiled.memory_analysis().temp_size_in_bytes < 476_339_968


def test_delta_rule_reads_q_k_and_v_where_the_convolution_wrote_them(one_chip, monkeypatch):
    """At the published widths the rule's three sequence operands are one array, the 64 heads
    ``[q | k | v]`` that the fusion after the convolution writes. Cutting q, k and v out of a
    96-head in-projection result (``slice``) and repeating q and k to 32 heads (two
    ``broadcast``s of 268 MB) would each leave an instruction of its own: no instruction of
    8,192 x 128 heads is anything but a fusion, its part of a multi-output fusion, or the
    kernel."""
    monkeypatch.setattr(gated_delta, "_use_interpreter", lambda: False)
    config = model.load_config("benchmarks/configs/qwen3next_80b_a3b_ep4.json")
    layer = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
                         qwen3_next.param_shapes(config)["layer_0"]["mixer"])
    x = jax.ShapeDtypeStruct((2, 8192, config["hidden_size"]), jnp.float32, sharding=one_chip)
    net = jax.jit(lambda p, x: qwen3_next.gated_delta_net(p, x, config))
    text = net.lower(layer, x).compile().as_text()
    entry = text[text.index("\nENTRY "):].split("\n}", 1)[0]
    call, = _hlo_text.kernel_calls(entry, "gdn_scan")
    q, k, v = _hlo_text.operands(call)[:3]
    assert q == k == v
    defined = {_hlo_text.name_of(line): line for line in entry.splitlines()
               if _hlo_text._DEFINES.match(line)}
    assert " = f32[2,64,8192,128]{" in defined[q] and " fusion(" in defined[q]
    for name, line in defined.items():
        if "8192,128]" in line.split(" = ", 1)[1].split(" ", 1)[0]:
            kind = re.search(r" ([a-z][\w-]*)\(%", line).group(1)
            assert kind in ("fusion", "get-tuple-element", "custom-call"), line[:160]
    # the convolution reads the in-projection's [q | k | v] product whole, with no slice
    # of a wider result between them
    products = [line for line in defined.values() if " = f32[2,64,8192,128]{" in line
                and _hlo_text.is_product_fusion(text, line)]
    assert products and not any(" = f32[2,96,8192,128]{" in line for line in defined.values())


def test_attention_kernel_compiles_for_the_chip_at_published_widths(one_chip, monkeypatch):
    monkeypatch.setattr(attention_op, "_use_interpreter", lambda: False)
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda q, k, v: attention_op.causal_attention(q, k, v, 1.0 / 16))
    compiled = fn.lower(spec((2, 8192, 16, 256)), spec((2, 8192, 2, 256)),
                        spec((2, 8192, 2, 256))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%attention" in text
    # the scores never exist: the largest temporary is the heads-first copy of q
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 * 8192 * 16 * 256 * 4
