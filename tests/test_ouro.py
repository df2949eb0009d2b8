"""Ouro on the CPU at small widths: the program (``models/ouro.py`` over ``lm_blocks`` and
``ops/attention.py``) against the benchmark's plain reference (``benchmarks/reference/ouro.py``),
which shares no code with it. float32 parameters make the program's products exact, so the
mathematics is held to 1e-5; bfloat16 parameters are the configuration as it runs, held to
what that rounding gives. Then what makes it a loop: one set of weights, one layer body in
the program whatever ``total_ut_steps`` says; and, last, the whole step compiled for a
described v5e at the published widths and depth."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo_text
from benchmarks import lm_weights, model
from benchmarks.comparers import logprob_rows_looped
from benchmarks.comparers.logprob_rows import row_gaps
from benchmarks.drivers import token_stream_looped
from sparkdl_tpu.models import lm_blocks, ouro
from sparkdl_tpu.ops import attention as attention_op

SEED = 2**31 + 7
TOKENS = 48


def small_config(**changes):
    config = dict(
        reference="ouro", program={"module": "ouro"}, head="logprobs", input_shape=[TOKENS],
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, intermediate_size=96, vocab_size=128, rms_norm_eps=1e-6,
        rope_theta=1000000, rope_scaling=None, total_ut_steps=4, early_exit_threshold=1,
        tie_word_embeddings=False, assumed={"head_gain": 2.0, "exit_gate_gain": 1.0})
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def small():
    config = small_config()
    weights = lm_weights.make_weights(config, SEED)
    tokens = lm_weights.token_rows(SEED, 4, TOKENS, config["vocab_size"], 1.0)
    return config, weights, tokens


def _as(weights, dtype):
    return weights if dtype == "bfloat16" else {k: v.astype(jnp.float32) for k, v in weights.items()}


# bfloat16: the rehearsal's own limits (benchmarks/traffic/tokens_stream_4k.json)
@pytest.mark.parametrize("dtype, limit, pdf_limit", [("float32", 1e-5, 1e-5),
                                                     ("bfloat16", 0.05, 0.0023)])
def test_program_matches_reference(small, dtype, limit, pdf_limit):
    config, weights, tokens = small
    ref, ref_pdf = logprob_rows_looped.reference_outputs(config, weights, tokens)
    assert ref.shape == (4, TOKENS - 1) and ref.std(axis=1).min() > 1.0  # not flat
    assert ref_pdf.shape == (4, 4) and 0.05 < ref_pdf.min() and ref_pdf.max() < 0.6
    mf = token_stream_looped.model_function(config, _as(weights, dtype), TOKENS)
    assert mf.output_names == ["logprobs", "exit_pdf"] and mf.name == "Ouro"
    out = mf({"tokens": tokens})
    assert out["logprobs"].dtype == jnp.float32 and out["logprobs"].shape == (4, TOKENS - 1)
    assert out["exit_pdf"].dtype == jnp.float32 and out["exit_pdf"].shape == (4, 4)
    assert row_gaps(out["logprobs"], ref).max() < limit
    assert np.abs(np.asarray(out["exit_pdf"]) - ref_pdf).max() < pdf_limit


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_every_count_of_passes_is_a_model_of_its_own_and_matches_the_reference(small, passes):
    config, weights, tokens = small
    config = dict(config, total_ut_steps=passes)
    exact = _as(weights, "float32")
    ref, ref_pdf = logprob_rows_looped.reference_outputs(config, weights, tokens)
    out = token_stream_looped.model_function(config, exact, TOKENS)({"tokens": tokens})
    assert row_gaps(out["logprobs"], ref).max() < 1e-5
    assert out["exit_pdf"].shape == (4, passes)
    np.testing.assert_allclose(out["exit_pdf"], ref_pdf, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["exit_pdf"]).sum(axis=1), 1.0, atol=1e-6)
    # the reference's own keyword gives the same model as the configuration's key
    if passes < 4:
        short, _ = logprob_rows_looped.reference_outputs(small[0], weights, tokens, passes=passes)
        np.testing.assert_allclose(short, ref, rtol=1e-5, atol=1e-5)
        full, _ = logprob_rows_looped.reference_outputs(small[0], weights, tokens)
        assert row_gaps(ref, full).min() > 0.1  # another count of passes: another answer


@pytest.mark.parametrize("fault", [{"passes": 3}, {"norm_in_loop": False}, {"quant": "int8"}])
def test_a_broken_reference_is_another_model(small, fault):
    config, weights, tokens = small
    ref, ref_pdf = logprob_rows_looped.reference_outputs(config, weights, tokens)
    broken, broken_pdf = logprob_rows_looped.reference_outputs(config, weights, tokens, **fault)
    assert row_gaps(broken, ref).min() > (0.03 if "quant" in fault else 0.3)
    if "norm_in_loop" in fault:  # the gate reads the norm's output in both, the stream differs
        assert np.abs(broken_pdf - ref_pdf).max() > 0.02


def test_exit_pdf_against_a_hand_computation():
    g = jnp.asarray(np.random.default_rng(5).normal(size=(4, 2, 7)), jnp.float32)
    lam = 1 / (1 + np.exp(-np.asarray(g, np.float64)))
    p = np.stack([lam[0], lam[1] * (1 - lam[0]), lam[2] * (1 - lam[0]) * (1 - lam[1]),
                  (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    got = np.asarray(ouro.exit_pdf(g))
    assert got.shape == (2, 4)
    np.testing.assert_allclose(got, p.mean(axis=-1).T, rtol=1e-5)
    np.testing.assert_allclose(got.sum(axis=1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(ouro.exit_pdf(g[:1]), np.ones((2, 1), np.float32))  # one pass


def test_the_tree_holds_one_leaf_a_layer_kind(small):
    config, weights, _ = small
    shapes = ouro.param_shapes(config)
    assert set(shapes) == {"embed", "layers", "final_norm", "exit_gate", "head"}
    assert set(shapes["layers"]) == {"q_proj", "k_proj", "v_proj", "o_proj", "gate", "up", "down",
                                     "norm1", "norm2", "norm3", "norm4"}
    assert all(leaf.shape[0] == 3 for leaf in shapes["layers"].values())
    assert shapes["layers"]["gate"].shape == (3, 64, 96) and shapes["layers"]["gate"].dtype == jnp.bfloat16
    assert shapes["layers"]["norm3"].shape == (3, 64) and shapes["layers"]["norm3"].dtype == jnp.float32
    assert shapes["exit_gate"]["weight"].dtype == jnp.float32 and shapes["exit_gate"]["bias"].shape == (1,)
    # the benchmark's weights are that tree, leaf for leaf: a stacked leaf is one array
    mf = token_stream_looped.model_function(config, weights, TOKENS)
    assert mf.params["layers"]["q_proj"] is weights["layers/q_proj"]
    assert jax.tree_util.tree_structure(mf.params) == jax.tree_util.tree_structure(shapes)
    with pytest.raises(ValueError):
        ouro.param_shapes(dict(config, tie_word_embeddings=True))


def _program_text(config, passes):
    config = dict(config, total_ut_steps=passes)
    shapes = ouro.param_shapes(config)
    tokens = jax.ShapeDtypeStruct((2, TOKENS), jnp.int32)
    return str(jax.make_jaxpr(lambda p, t: ouro.forward(p, t, config))(shapes, tokens))


def test_the_program_holds_one_layer_body_whatever_the_count_of_passes(small):
    config = small[0]
    two, four = _program_text(config, 2), _program_text(config, 4)
    assert two.count("pallas_call") == four.count("pallas_call") == 1  # three layers, four passes
    assert "name=attention" in four
    assert len(two.splitlines()) == len(four.splitlines())
    # written out, a layer at a time, the text grows with the depth: that is what the loop saves
    deeper = _program_text(dict(config, num_hidden_layers=6), 4)
    assert len(deeper.splitlines()) == len(four.splitlines())


def test_the_published_configuration_counts_2_667_974_657_parameters():
    config = model.load_config("benchmarks/configs/ouro_2p6b.json")
    leaves = jax.tree_util.tree_leaves(ouro.param_shapes(config))  # shapes only, nothing allocated
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == 2_667_974_657
    in_bf16 = sum(int(np.prod(leaf.shape)) for leaf in leaves if leaf.dtype == jnp.bfloat16)
    assert in_bf16 == 48 * 51_380_224 + 2 * 49_152 * 2_048  # the matrices; the rest float32
    assert 2_667_974_657 - in_bf16 == 4 * 48 * 2_048 + 2_048 + 2_048 + 1


def test_an_exit_threshold_or_a_rope_scaling_that_is_not_served_is_refused(small):
    config, weights, tokens = small
    tree = token_stream_looped.model_function(config, weights, TOKENS).params
    with pytest.raises(ValueError):
        ouro.forward(tree, jnp.asarray(tokens), dict(config, early_exit_threshold=0.5))
    with pytest.raises(ValueError):
        ouro.rotary_inv_freq(dict(config, rope_scaling={"type": "yarn", "factor": 4}))
    np.testing.assert_allclose(ouro.rotary_inv_freq(config),
                               [1e6 ** (-2 * i / 16) for i in range(8)], rtol=1e-12)


def test_record_exit_counts_rows_and_sets_the_mean_exit_step():
    from sparkdl_tpu.obs.registry import MetricsRegistry
    registry = MetricsRegistry()
    pdf = np.array([[0.1, 0.2, 0.3, 0.4], [0.4, 0.3, 0.2, 0.1]])
    ouro.record_exit(pdf.sum(axis=0), 2, registry=registry)
    seen = registry.snapshot()
    assert seen["loop.rows"] == 2 and set(seen) == {"loop.rows", "loop.exit_step_mean"}
    assert seen["loop.exit_step_mean"] == pytest.approx(2.5)  # (3.0 + 2.0) / 2
    ouro.record_exit(pdf[0], 1, registry=registry)  # counters add, the gauge is the last call's
    seen = registry.snapshot()
    assert seen["loop.rows"] == 3 and seen["loop.exit_step_mean"] == pytest.approx(3.0)
    ouro.record_exit(np.zeros(4), 0, registry=registry)  # an empty window moves nothing
    assert registry.snapshot()["loop.exit_step_mean"] == pytest.approx(3.0)


def test_scoring_function_takes_the_outputs_names_from_its_caller():
    def forward(params, tokens, config, **flags):
        assert not flags  # a model's own switch is bound by the model, not passed here
        return {"logprobs": jnp.zeros((tokens.shape[0], 3)), "extra": jnp.ones((tokens.shape[0], 2))}

    named = lm_blocks.scoring_function(forward, {}, {}, seq_len=4, name="M", outputs=["logprobs", "extra"])
    assert named.output_names == ["logprobs", "extra"]
    assert set(named({"tokens": np.zeros((2, 4), np.int32)})) == {"logprobs", "extra"}
    with pytest.raises(TypeError):  # no default list: the names are the caller's to give
        lm_blocks.scoring_function(forward, {}, {}, seq_len=4, name="M")


def test_through_tensor_transformer_both_columns_come_back_in_order(small, loaded_ahead):
    from benchmarks.drivers.stream import _partitions
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.data.tensors import arrow_to_tensor
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer

    config, weights, _ = small
    tokens = lm_weights.token_rows(SEED + 1, 10, TOKENS, config["vocab_size"], 1.0)
    mf = token_stream_looped.model_function(config, weights, TOKENS)
    t = TensorTransformer(modelFunction=mf, inputMapping={"tokens": "tokens"},
                          outputMapping={"logprobs": "logprobs", "exit_pdf": "exit_pdf"}, batchSize=2)
    out = t.transform(DataFrame.from_batches(_partitions(tokens, 5, 2, 5, "tokens"))).collect()
    scores, pdf = arrow_to_tensor(out.column("logprobs")), arrow_to_tensor(out.column("exit_pdf"))
    assert scores.shape == (10, TOKENS - 1) and scores.dtype == np.float32
    assert pdf.shape == (10, 4) and pdf.dtype == np.float32
    direct = [mf({"tokens": tokens[lo:lo + 2]}) for lo in range(0, 10, 2)]
    np.testing.assert_allclose(scores, np.concatenate([d["logprobs"] for d in direct]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(pdf, np.concatenate([d["exit_pdf"] for d in direct]), atol=1e-5)
    assert len({row.tobytes() for row in scores}) == 10  # ten rows, ten answers
    assert t.metrics.boundary_carried == 1 and t.metrics.boundary_cold == 0


def test_random_params_fill_the_tree_the_builder_describes():
    config = small_config()
    shapes = ouro.param_shapes(config)
    params = ouro.random_params(config, seed=3)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(shapes)
    for leaf, spec in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(shapes)):
        assert leaf.shape == spec.shape and leaf.dtype == spec.dtype
    norms = np.asarray(params["layers"]["norm2"])
    assert 0.9 <= norms.min() and norms.max() <= 1.1
    assert abs(float(np.asarray(params["exit_gate"]["weight"]).std()) * 8 - 1.0) < 0.3  # 1 / sqrt(64)
    # a stacked matrix is drawn at 1 / sqrt(fan_in) of a layer's matrix, not of the stack
    assert abs(float(np.asarray(params["layers"]["down"], np.float32).std()) * np.sqrt(96) - 1.0) < 0.1
    mf = ouro.model_function(config, params, seq_len=20)
    rows = np.random.default_rng(0).integers(0, 128, size=(3, 20)).astype(np.int32)
    out = mf({"tokens": rows})
    scores = np.asarray(out["logprobs"])
    assert scores.shape == (3, 19) and np.isfinite(scores).all() and (scores < 0).all()
    pdf = np.asarray(out["exit_pdf"])
    np.testing.assert_allclose(pdf.sum(axis=1), 1.0, atol=1e-6)
    assert pdf.min() > 0.02 and pdf.max() < 0.8  # the gate spreads: every pass gets a share
    # causal: a row's early scores do not depend on its later tokens
    changed = rows.copy()
    changed[:, 12:] = (changed[:, 12:] + 1) % 128
    again = np.asarray(mf({"tokens": changed})["logprobs"])
    np.testing.assert_allclose(again[:, :11], scores[:, :11], rtol=1e-4, atol=1e-5)
    assert not np.allclose(again[:, 12:], scores[:, 12:])


# -- the whole step at the published widths and depth, compiled for the chip without it ---

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_step_compiles_for_the_chip_with_one_layer_body_and_no_copy_of_a_weight(one_chip, monkeypatch):
    """48 layers four times over: 192 applications, one `attention` call in the compiled
    text, two loops, and temporaries that could not hold a second copy of the 4.9 GB of
    stacked matrices (the program's own: the head's logits, the SwiGLU's intermediates)."""
    monkeypatch.setattr(attention_op, "_use_interpreter", lambda: False)
    config = model.load_config("benchmarks/configs/ouro_2p6b.json")
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), ouro.param_shapes(config))
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: ouro.forward(p, t, config)).lower(
        shapes, tokens).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "%attention" in calls[0]
    assert "/ut_loop/while/body/closed_call/while/body/closed_call/attention" in calls[0]
    memory = compiled.memory_analysis()
    assert abs(memory.argument_size_in_bytes - (2 * 2_667_577_344 + 4 * 397_313 + 2 * 4096 * 4)) < 2**20
    assert memory.temp_size_in_bytes < 3_600_000_000  # 3,063,716,864 here; a copied stack is +4.9 GB
    # the kernel writes o_proj's operand: [B, T, Hq * dv] in the matrix's type, taken by the
    # product's fusion with no cast or copy between (the parent, d781583: a float32
    # [2,16,4096,128], then `convert_bitcast_fusion.2`, then `copy.68`)
    assert " bf16[2,4096,2048]{2,1,0" in calls[0] and "[2,16,4096,128]" not in calls[0].split(" custom-call(")[0]
    takers = _hlo_text.users(text, _hlo_text.name_of(calls[0]))
    assert len(takers) == 1 and _hlo_text.is_product_fusion(text, takers[0]), takers
    # q, k and v stay heads-first copies that ride in the rotary's and v's own fusions: no
    # `copy` feeds the kernel, and the step holds 12 in all (the parent 13; with the three
    # operands read in place 15)
    assert not [name for name in _hlo_text.operands(calls[0]) if name.startswith("copy")]
    assert len(re.findall(r" copy\(", text)) <= 13
