"""A.X-K1 on the CPU at small widths: the program (``models/axk1.py`` over
``ops/attention.py`` and ``ops/moe.py``) against the benchmark's plain reference
(``benchmarks/reference/axk1.py``), which shares no code with it. float32 parameters
make the program's products exact, so the mathematics is held to 1e-5; bfloat16
parameters are the configuration as it runs, held to what that rounding gives. The
kernels' tests at shapes both token models use are cases of ``tests/test_qwen3_next.py``;
here are the ones only this model's shapes reach, and, last, both kernels compiled for a
described v5e at the published widths."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo_text
from benchmarks import lm_weights, model
from benchmarks.comparers.logprob_rows import row_gaps
from benchmarks.drivers import token_stream_routed
from benchmarks.reference import axk1 as reference
from benchmarks.reference.nn import Net
from sparkdl_tpu.models import axk1
from sparkdl_tpu.ops import attention as attention_op
from sparkdl_tpu.ops import moe

SEED = 2**31 + 7
PUBLISHED_ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"}


def small_config(**changes):
    """One dense layer and three that route, 8 experts of which 3 are held, in the
    benchmark file's convention: ``n_routed_experts`` counts the experts held,
    ``router_width`` the router's outputs."""
    config = dict(
        reference="axk1", program={"module": "axk1"}, head="logprobs", input_shape=[48],
        hidden_size=64, num_hidden_layers=4, first_k_dense_replace=1, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32, n_shared_experts=1, n_routed_experts=3,
        router_width=8, experts_held=[2, 5], num_experts_per_tok=2, scoring_func="sigmoid",
        routed_scaling_factor=2.5, vocab_size=128, rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling=dict(PUBLISHED_ROPE, original_max_position_embeddings=16),
        assumed={"head_gain": 2.0})
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def small():
    config = small_config()
    weights = lm_weights.make_weights(config, SEED)
    tokens = lm_weights.token_rows(SEED, 4, 48, config["vocab_size"], 1.0)
    return config, weights, tokens


def _as(weights, dtype):
    return weights if dtype == "bfloat16" else {k: v.astype(jnp.float32) for k, v in weights.items()}


# bfloat16: the rehearsal's own limit; a flipped choice moves a row of 47 tokens by a tenth
@pytest.mark.parametrize("dtype, limit", [("float32", 1e-5), ("bfloat16", 0.3)])
def test_program_matches_reference_on_logprobs(small, dtype, limit):
    config, weights, tokens = small
    ref = lm_weights.reference_outputs(config, weights, tokens)
    assert ref.shape == (4, 47) and ref.std(axis=1).min() > 1.0  # not flat
    mf = token_stream_routed.model_function(config, _as(weights, dtype), 48)
    assert mf.output_names == ["logprobs"] and mf.name == "AXK1"
    out = mf(tokens)
    assert out.dtype == jnp.float32 and out.shape == (4, 47)
    assert row_gaps(out, ref).max() < limit


@pytest.mark.parametrize("dtype, limit", [("float32", 1e-5), ("bfloat16", 0.2)])
def test_program_matches_reference_on_logits(small, dtype, limit):
    config, weights, tokens = small
    ref = lm_weights.reference_outputs(dict(config, head="logits"), weights, tokens)
    assert ref.shape == (4, 47, 128)
    mf = token_stream_routed.model_function(config, _as(weights, dtype), 48)
    hidden, _ = axk1.final_hidden(mf.params, jnp.asarray(tokens), config)
    logits = jnp.dot(hidden[:, :-1].astype(jnp.float32), mf.params["head"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    assert np.linalg.norm(np.asarray(logits) - ref) / np.linalg.norm(ref) < limit


def test_a_reference_without_the_shared_rotary_key_is_another_model(small):
    config, weights, tokens = small
    ref = lm_weights.reference_outputs(config, weights, tokens)
    broken = jax.jit(lambda w, t: reference.forward(
        Net(params=w), t, config, use_rope_key=False)["logprobs"])(weights, tokens)
    assert row_gaps(np.asarray(broken), ref).min() > 0.1


def test_yarn_frequencies_against_hand_values():
    """The published scaling over 64 rotary columns: the blend runs from pair 10 (which
    turns 32 times in 4,096 positions) to pair 23 (which turns once)."""
    config = {"qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": PUBLISHED_ROPE}
    inv_freq = axk1.yarn_inv_freq(config)
    assert inv_freq.shape == (32,) and inv_freq.dtype == np.float64
    f = lambda i: 10000.0 ** (-2 * i / 64)
    assert inv_freq[0] == pytest.approx(1.0, rel=1e-12)  # kept
    assert inv_freq[10] == pytest.approx(f(10), rel=1e-12)  # the last one kept whole
    assert inv_freq[23] == pytest.approx(f(23) / 32, rel=1e-12)  # the first slowed in full
    assert inv_freq[31] == pytest.approx(f(31) / 32, rel=1e-12)
    keep = 1 - (16 - 10) / (23 - 10)  # between: blended linearly in the pair's index
    assert inv_freq[16] == pytest.approx(f(16) / 32 * (1 - keep) + f(16) * keep, rel=1e-12)
    np.testing.assert_allclose(inv_freq, reference.yarn_inv_freq(config), rtol=1e-12)
    # 192^-0.5 times (0.1 ln 32 + 1)^2
    assert axk1.softmax_scale(dict(config, qk_nope_head_dim=128)) == pytest.approx(
        192 ** -0.5 * 1.3465735902799727 ** 2, rel=1e-12)
    with pytest.raises(ValueError):  # cos and sin would need a factor that nothing applies
        axk1.softmax_scale(dict(config, qk_nope_head_dim=128,
                                rope_scaling=dict(PUBLISHED_ROPE, mscale=0.5)))
    plain = axk1.yarn_inv_freq(dict(config, rope_scaling=None))
    np.testing.assert_allclose(plain, [f(i) for i in range(32)], rtol=1e-12)


def test_the_sigmoid_router_gives_weights_that_sum_to_the_scaling_factor():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(40, 192)) * 3, jnp.float32)
    experts, weights = moe.route(logits, 8, scoring="sigmoid", scale=2.5)
    assert experts.shape == (40, 8) and experts.dtype == jnp.int32
    np.testing.assert_allclose(weights.sum(axis=-1), 2.5, rtol=1e-6)
    # the eight largest scores, each one's own sigmoid over the eight's sum
    order = np.argsort(-np.asarray(logits), axis=1)[:, :8]
    np.testing.assert_array_equal(np.sort(experts, axis=1), np.sort(order, axis=1))
    s = 1 / (1 + np.exp(-np.take_along_axis(np.asarray(logits, np.float64), np.asarray(experts), 1)))
    np.testing.assert_allclose(weights, 2.5 * s / s.sum(axis=1, keepdims=True), rtol=1e-5)
    # the softmax router is what it was: probabilities renormalised to 1
    _, plain = moe.route(logits, 8)
    np.testing.assert_allclose(plain.sum(axis=-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError):
        moe.route(logits, 8, scoring="tanh")


def _layer_params(rng, config, experts):
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    leaf = lambda *shape: jnp.asarray(rng.normal(size=shape) / math.sqrt(shape[-2]), jnp.float32)
    return {"router": leaf(d, experts), "experts_gate": leaf(experts, d, f),
            "experts_up": leaf(experts, d, f), "experts_down": leaf(experts, f, d),
            "shared_gate": leaf(d, f), "shared_up": leaf(d, f), "shared_down": leaf(f, d)}


def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """Shares [0,2) .. [30,32) of 32 experts, each computed by the program with its own
    slice of the matrices, the shared expert counted once, against the reference's layer
    with all 32."""
    rng = np.random.default_rng(11)
    experts = 32
    base = small_config(hidden_size=32, moe_intermediate_size=16, num_experts_per_tok=3)
    whole = _layer_params(rng, base, experts)
    x = jnp.asarray(rng.normal(size=(2, 24, 32)), jnp.float32)
    uncut = dict(base, n_routed_experts=experts, router_width=experts, experts_held=[0, experts])
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
        config = dict(base, router_width=experts, experts_held=[first, first + 2])
        y, chosen = axk1.sparse_moe(share, x, config)
        assert chosen.shape == (48, 3)
        total = total + y
    np.testing.assert_allclose(total, expected, rtol=1e-4, atol=1e-5)


def test_latent_attention_is_computed_once_whatever_share_of_the_experts_is_held(small):
    """MLA and the dense layer hold nothing of ``experts_held``: the same parameters under
    another share give the same block output."""
    config, weights, tokens = small
    mf = token_stream_routed.model_function(config, _as(weights, "float32"), 48)
    p = mf.params["layer_1"]["mixer"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 48, 64)), jnp.float32)
    here = axk1.latent_attention(p, x, config)
    elsewhere = axk1.latent_attention(p, x, dict(config, experts_held=[5, 8]))
    np.testing.assert_array_equal(here, elsewhere)
    flat = {k.split("/", 1)[1]: v.astype(jnp.float32) for k, v in weights.items()
            if k.startswith("Layer_1/LatentAttention_0/")}
    expected = reference.latent_attention(Net(params=flat), x, config)
    np.testing.assert_allclose(here, expected, rtol=1e-4, atol=2e-5)
    # the reference's walk over blocks of query rows gives what one block gives
    in_blocks = reference.latent_attention(Net(params=flat), x, config, block_rows=16)
    np.testing.assert_allclose(in_blocks, expected, rtol=1e-5, atol=1e-5)


def test_routing_has_a_row_for_each_layer_that_routes_and_none_for_the_dense_one(small):
    config, weights, tokens = small
    mf = token_stream_routed.model_function(config, weights, 48, routing_stats=True)
    assert mf.output_names == ["logprobs", "routing"]
    out = mf({"tokens": tokens})
    routing = np.asarray(out["routing"])
    assert routing.shape == (4, 3, 1 + 3) and routing.dtype == np.int32  # 4 layers, 3 route
    np.testing.assert_array_equal(routing[..., 0], routing[..., 1:].sum(axis=-1))
    assert 0 < routing[..., 0].max() <= 48 * 2
    _, counts = lm_weights.reference_outputs(config, _as(weights, "float32"), tokens, routing=True)
    exact = token_stream_routed.model_function(config, _as(weights, "float32"), 48,
                                               routing_stats=True)({"tokens": tokens})
    np.testing.assert_array_equal(np.asarray(exact["routing"])[..., 1:], counts)
    plain = token_stream_routed.model_function(config, weights, 48)(tokens)
    np.testing.assert_allclose(out["logprobs"], plain, rtol=1e-5, atol=1e-6)

    from sparkdl_tpu.models import qwen3_next
    from sparkdl_tpu.obs.registry import MetricsRegistry
    assert qwen3_next.record_routing is moe.record_routing  # the name it had, by import
    registry = MetricsRegistry()
    moe.record_routing(routing, assignments=4 * 48 * 2 * 3, registry=registry)
    seen = registry.snapshot()
    assert seen["moe.assignments"] == 4 * 48 * 2 * 3
    assert seen["moe.assignments_held"] == routing[..., 0].sum()
    assert seen["moe.expert_load_max"] == routing.sum(axis=0)[:, 1:].max()


def test_through_tensor_transformer_across_a_partition_boundary(small, loaded_ahead):
    from benchmarks.drivers.stream import _partitions
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.data.tensors import arrow_to_tensor
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer

    config, weights, _ = small
    tokens = lm_weights.token_rows(SEED + 1, 10, 48, config["vocab_size"], 1.0)
    mf = token_stream_routed.model_function(config, weights, 48)
    t = TensorTransformer(modelFunction=mf, inputMapping={"tokens": "tokens"},
                          outputMapping={"logprobs": "logprobs"}, batchSize=2)
    out = t.transform(DataFrame.from_batches(_partitions(tokens, 5, 2, 5, "tokens"))).collect()
    scores = arrow_to_tensor(out.column("logprobs"))
    assert scores.shape == (10, 47) and scores.dtype == np.float32
    direct = np.concatenate([np.asarray(mf(tokens[lo:lo + 2])) for lo in range(0, 10, 2)])
    np.testing.assert_allclose(scores, direct, rtol=1e-4, atol=1e-4)
    assert t.metrics.boundary_carried == 1 and t.metrics.boundary_cold == 0


def test_random_params_fill_the_tree_the_builder_describes():
    config = dict(small_config(), n_routed_experts=8)  # the uncut model's own key, unused here
    shapes = axk1.param_shapes(config)
    params = axk1.random_params(config, seed=3)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(shapes)
    for leaf, spec in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(shapes)):
        assert leaf.shape == spec.shape and leaf.dtype == spec.dtype
    assert params["layer_1"]["moe"]["experts_gate"].shape == (3, 64, 32)
    assert set(params["layer_0"]) == {"norm1", "norm2", "mixer", "mlp"}
    assert set(params["layer_3"]) == {"norm1", "norm2", "mixer", "moe"}
    assert params["layer_0"]["mixer"]["kv_a_proj"].shape == (64, 16 + 8)
    assert params["layer_0"]["mixer"]["q_a_norm"].dtype == jnp.float32
    mf = axk1.model_function(config, params, seq_len=20)
    rows = np.random.default_rng(0).integers(0, 128, size=(3, 20)).astype(np.int32)
    scores = np.asarray(mf(rows))
    assert scores.shape == (3, 19) and np.isfinite(scores).all() and (scores < 0).all()
    # causal: a row's early scores do not depend on its later tokens
    changed = rows.copy()
    changed[:, 12:] = (changed[:, 12:] + 1) % 128
    again = np.asarray(mf(changed))
    np.testing.assert_allclose(again[:, :11], scores[:, :11], rtol=1e-4, atol=1e-5)
    assert not np.allclose(again[:, 12:], scores[:, 12:])


# -- the kernels at the published widths, compiled for the chip without it ------------

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
    """An expert of 7,168 x 2,048 is 88 MB: walked in blocks of its width, in tiles of 256
    rows, where the parent's kernel asked for 178 MB of the core's 128 MiB."""
    monkeypatch.setattr(moe, "_use_interpreter", lambda: False)
    held, d, f = 12, 7168, 2048
    assert moe.width_block(d, f, 2) == 512 and moe.row_tile(d, f, 2) == 256
    assert moe.width_block(2048, 512, 2) == 512 and moe.row_tile(2048, 512, 2) == 128
    tile = moe.row_tile(d, f, 2)
    n = 16384
    rows = moe.layout_rows(n * 8, held, tile)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = jax.jit(lambda *a: moe.grouped_swiglu(*a, tile=tile))
    compiled = fn.lower(
        spec((n, d), jnp.float32), spec((rows,), jnp.int32), spec((rows // tile,), jnp.int32),
        spec((), jnp.int32), spec((held, d, f), jnp.bfloat16), spec((held, d, f), jnp.bfloat16),
        spec((held, f, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%moe_experts" in text and "%moe_slabs" in text


def test_expert_block_compiles_for_the_chip_at_published_widths(one_chip, monkeypatch):
    monkeypatch.setattr(moe, "_use_interpreter", lambda: False)
    n, k, held, d, f = 16384, 8, 12, 7168, 2048
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
    # no grouped copy of the tokens' rows: the parent's gather (d1cff70) wrote a bfloat16
    # [134,144, 7,168], 1.92 GB of which the kernel read the 7% of the tiles in use
    rows = moe.layout_rows(n * k, held, moe.row_tile(d, f, 2))
    assert f"[{rows},{d}]" not in text
    # the parent compiles to 4,122,403,840 bytes of temporaries at these shapes, the row
    # copies to 2,198,170,112 (the kernel's result, sized for every assignment, is what is
    # left); the limit lies halfway
    assert compiled.memory_analysis().temp_size_in_bytes < 3_160_286_976


def test_latent_attention_kernel_compiles_for_the_chip_at_published_widths(one_chip, monkeypatch):
    """64 heads of 128 + 64 against 128-wide values, the rotary key one head."""
    monkeypatch.setattr(attention_op, "_use_interpreter", lambda: False)
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda q, k, v, qr, kr: attention_op.causal_attention(
        q, k, v, 192 ** -0.5, rope=(qr, kr)))
    compiled = fn.lower(spec(2, 8192, 64, 128), spec(2, 8192, 64, 128), spec(2, 8192, 64, 128),
                        spec(2, 8192, 64, 64), spec(2, 8192, 1, 64)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%attention" in text
    # neither the scores nor a copy of the rotary key for every head ever exist: the
    # temporaries are the heads-first bfloat16 copies of the operands and the float32 output
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 * 8192 * 64 * (192 + 256 + 256) * 2


def test_a_cut_of_the_step_compiles_for_the_chip_with_nothing_heads_first_round_the_kernel(one_chip, monkeypatch):
    """Cell 5's `forward` at the published widths, cut to its dense layer and one that routes
    so that the compile fits a test: the kernel reads `[k_nope | v]` out of `kv_b_proj`'s
    product itself and writes `o_proj`'s operand. The parent (d781583) held 12 arrays of
    `[2,64,8192,128]` a layer (the heads-first copies of q, k and v, the float32 output, its
    cast and the copy after it)."""
    monkeypatch.setattr(attention_op, "_use_interpreter", lambda: False)
    monkeypatch.setattr(moe, "_use_interpreter", lambda: False)
    config = dict(model.load_config("benchmarks/configs/axk1_ep16.json"), num_hidden_layers=2)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), axk1.param_shapes(config))
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda p, t: axk1.forward(p, t, config)).lower(shapes, tokens).compile().as_text()
    assert "[2,64,8192,128]" not in text
    calls = _hlo_text.kernel_calls(text, "attention")
    assert len(calls) == 2
    for call in calls:
        assert " bf16[2,8192,8192]{2,1,0" in call  # [B, T, Hq * dv], in o_proj's type
        # the second and third operands are one array: the product that rebuilds k_nope and v
        _, k, v, _, _ = _hlo_text.operands(call)
        assert k == v and k.startswith("convolution")
        takers = _hlo_text.users(text, _hlo_text.name_of(call))
        assert len(takers) == 1 and _hlo_text.is_product_fusion(text, takers[0]), takers
