"""useMesh pipeline-surface tests (multi-chip DP inference through the
transformers; tests run on the 8 simulated CPU devices) and the Spark
binding seam."""

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.data import DataFrame
from sparkdl_tpu.data.frame import Stage
from sparkdl_tpu.data.spark_binding import (
    SparkEngine,
    plan_to_map_in_arrow,
)
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.parallel.inference import ShardedBatchRunner
from sparkdl_tpu.runtime.runner import BatchRunner
from sparkdl_tpu.transformers import (
    DeepImageFeaturizer,
    ImageTransformer,
    TensorTransformer,
)


@pytest.fixture(scope="module")
def image_df(tmp_path_factory):
    from PIL import Image
    rng = np.random.default_rng(21)
    d = tmp_path_factory.mktemp("meshimgs")
    for i in range(7):
        arr = rng.integers(0, 255, (20, 24, 3), dtype=np.uint8)
        Image.fromarray(arr, "RGB").save(d / f"m{i}.png")
    return imageIO.readImages(str(d), numPartitions=2)


class TestUseMesh:
    def test_featurizer_mesh_matches_single_device(self, image_df):
        single = DeepImageFeaturizer(modelName="TestNet", inputCol="image",
                                     outputCol="f", batchSize=2)
        sharded = DeepImageFeaturizer(modelName="TestNet", inputCol="image",
                                      outputCol="f", batchSize=2,
                                      useMesh=True)
        a = single.transform(image_df).tensor("f")
        b = sharded.transform(image_df).tensor("f")
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_device_resize_mesh_matches_single_device(self, image_df):
        """deviceResizeFrom + useMesh: the fused resize+model program
        shards over the data axis like any other model program."""
        kw = dict(modelName="TestNet", inputCol="image", outputCol="f",
                  batchSize=2, deviceResizeFrom=(20, 24))
        a = DeepImageFeaturizer(**kw).transform(image_df).tensor("f")
        b = DeepImageFeaturizer(useMesh=True, **kw) \
            .transform(image_df).tensor("f")
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_tensor_transformer_mesh(self):
        mf = ModelFunction.fromSingle(
            lambda x: x * 3.0, None, input_shape=(4,), name="triple")
        rows = [{"x": [float(i)] * 4} for i in range(10)]
        df = DataFrame.from_pylist(rows, num_partitions=2)
        t = TensorTransformer(modelFunction=mf,
                              inputMapping={"x": "input"},
                              outputMapping={"output": "y"},
                              batchSize=2, useMesh=True)
        got = t.transform(df).tensor("y")
        np.testing.assert_allclose(got[:, 0], np.arange(10) * 3.0,
                                   rtol=1e-6)

    @pytest.mark.parametrize("sizes", [
        [16, 16, 16], [14, 9, 21, 5], [8, 8, 8], [3, 2, 1], [16, 0, 16]])
    def test_tensor_transformer_mesh_carries_partition_boundaries(
            self, sizes, monkeypatch, loaded_ahead):
        """useMesh=True takes the engine's look ahead like the
        single-device path: same rows as without the mesh, and with
        every partition loaded each boundary is carried."""
        import jax

        from sparkdl_tpu.data.frame import Source
        from sparkdl_tpu.data.tensors import append_tensor_column
        monkeypatch.setattr(jax, "local_devices",
                            lambda *a, **k: jax.devices()[:4])
        rng = np.random.default_rng(9)
        batches = [append_tensor_column(
            pa.RecordBatch.from_pydict({"rid": pa.array(np.arange(n))}),
            "x", rng.normal(size=(n, 4)).astype(np.float32))
            for n in sizes]
        sources = [Source((lambda b=b: b), b.num_rows) for b in batches]
        mf = ModelFunction.fromSingle(
            lambda x: x * 3.0 - 1.0, None, input_shape=(4,), name="t")
        kw = dict(modelFunction=mf, inputMapping={"x": "input"},
                  outputMapping={"output": "y"}, batchSize=1)
        single = TensorTransformer(**kw)
        sharded = TensorTransformer(useMesh=True, **kw)
        a = single.transform(DataFrame(sources)).tensor("y")
        b = sharded.transform(DataFrame(sources)).tensor("y")
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        assert sharded.metrics.rows == sum(sizes)
        # blocks of 4 rows (the global batch): ceil(rows / 4) steps
        assert sharded.metrics.batches == -(-sum(sizes) // 4)
        if all(n % 4 == 0 and n for n in sizes):
            assert (sharded.metrics.boundary_carried,
                    sharded.metrics.boundary_cold) == (len(sizes) - 1, 0)

    def test_make_runner_selects_sharded(self):
        from sparkdl_tpu.transformers.utils import make_runner
        mf = ModelFunction.fromSingle(lambda x: x, None, input_shape=(2,))
        assert isinstance(make_runner(mf, 4, use_mesh=True),
                          ShardedBatchRunner)
        assert isinstance(make_runner(mf, 4, use_mesh=False), BatchRunner)

    def test_host_backend_falls_back_with_warning(self, caplog):
        import logging
        from sparkdl_tpu.transformers.utils import make_runner
        mf = ModelFunction(lambda p, i: i, None, {"x": ((2,), np.float32)},
                           output_names=["x"], backend="host")
        with caplog.at_level(logging.WARNING):
            r = make_runner(mf, 4, use_mesh=True)
        assert isinstance(r, BatchRunner)
        assert any("useMesh" in rec.message for rec in caplog.records)

    def test_sharded_program_cached_across_runners(self):
        """Two sharded runners over one model share the compiled program
        and the replicated weights (regression: per-runner re-jit and
        re-transfer)."""
        mf = ModelFunction.fromSingle(lambda x: x + 1.0, None,
                                      input_shape=(2,))
        r1 = ShardedBatchRunner(mf, batch_size=2)
        r2 = ShardedBatchRunner(mf, batch_size=4)
        x = np.zeros((8, 2), np.float32)
        r1.run({"input": x})
        r2.run({"input": x})
        assert r1.mesh == r2.mesh
        assert mf.sharded_jitted(r1.mesh) is mf.sharded_jitted(r2.mesh)


class TestSparkBinding:
    def test_plan_compiles_and_applies_without_spark(self):
        """plan_to_map_in_arrow is pure: it must run the stage chain
        over an Arrow batch iterator with no pyspark present."""
        def add_one(batch):
            vals = [v + 1 for v in batch.column(0).to_pylist()]
            return pa.RecordBatch.from_pydict({"x": pa.array(vals)})

        fn = plan_to_map_in_arrow([Stage(add_one, name="inc"),
                                   Stage(add_one, name="inc2")])
        batches = [pa.RecordBatch.from_pydict({"x": pa.array([1, 2])}),
                   pa.RecordBatch.from_pydict({"x": pa.array([10])})]
        out = list(fn(iter(batches)))
        assert [b.column(0).to_pylist() for b in out] == [[3, 4], [12]]

    def test_spark_engine_requires_pyspark(self):
        with pytest.raises(RuntimeError, match="pyspark"):
            SparkEngine()

    def test_executor_contract_real_plan_matches_local_engine(
            self, tmp_path_factory):
        """The full executor calling convention: a hand-built
        iterator-of-RecordBatches loop (what Spark's mapInArrow does on
        each task) over a REAL decode→resize/pack→model-apply plan must
        produce exactly what LocalEngine produces."""
        from PIL import Image
        rng = np.random.default_rng(33)
        d = tmp_path_factory.mktemp("bindimgs")
        for i in range(6):
            arr = rng.integers(0, 255, (16 + i, 20, 3), dtype=np.uint8)
            Image.fromarray(arr, "RGB").save(d / f"b{i}.png")

        df = imageIO.readImagesPacked(str(d), size=(8, 8),
                                      numPartitions=3)
        mf = ModelFunction.fromSingle(
            lambda x: x.reshape(x.shape[0], -1).astype("float32").sum(
                axis=1, keepdims=True),
            None, input_shape=(8, 8, 3), input_dtype=np.uint8,
            name="sum")
        out_df = TensorTransformer(modelFunction=mf,
                                   inputMapping={"image": "input"},
                                   outputMapping={"output": "s"},
                                   batchSize=4).transform(df)

        expected = out_df.collect()  # LocalEngine path

        # fake-executor loop: one task per partition source, each task
        # streams its batches through the compiled plan fn
        fn = plan_to_map_in_arrow(out_df._plan)
        got_batches = []
        for source in out_df._sources:
            got_batches.extend(fn(iter([source.load()])))
        got = pa.Table.from_batches(got_batches)

        assert got.schema == expected.schema
        assert got.column("filePath").to_pylist() == \
            expected.column("filePath").to_pylist()
        np.testing.assert_array_equal(
            np.asarray(got.column("s").combine_chunks().flatten()),
            np.asarray(expected.column("s").combine_chunks().flatten()))

    def test_executor_contract_with_index_stage(self):
        """with_index stages get the partition id (0 without a Spark
        TaskContext) — same convention LocalEngine now follows."""
        seen = []

        def probe(batch, index):
            seen.append(index)
            return batch

        fn = plan_to_map_in_arrow(
            [Stage(probe, name="probe", with_index=True)])
        batch = pa.RecordBatch.from_pydict({"x": pa.array([1])})
        list(fn(iter([batch])))
        assert seen == [0]


def test_yuv420_model_shards_on_mesh(tmp_path):
    """The 4:2:0 reconstruction op claims GSPMD-shardability (XLA-only
    einsum chain) — prove it: the same yuv420-wrapped model through the
    8-device ShardedBatchRunner must equal the single-device runner,
    through the full packed-reader flow, with a tail that pads."""
    from PIL import Image

    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.transformers.utils import (
        deviceResizeModel,
        single_io,
    )
    from sparkdl_tpu.utils.synth import textured_image

    rng = np.random.default_rng(9)
    for i in range(11):  # deliberately ragged vs 8-device global batch
        Image.fromarray(textured_image(rng, 40, 48), "RGB").save(
            tmp_path / f"m{i}.jpg", quality=90)
    mf = getModelFunction("TestNet", featurize=True)
    mfp = deviceResizeModel(mf, (24, 24), packedFormat="yuv420")
    in_name, out_name = single_io(mfp)
    packed = imageIO.readImagesPacked(str(tmp_path), (24, 24),
                                      numPartitions=3,
                                      packedFormat="yuv420")
    x = packed.tensor("image")

    single = BatchRunner(mfp, batch_size=4).run({in_name: x})[out_name]
    sharded = ShardedBatchRunner(mfp, batch_size=2).run(
        {in_name: x})[out_name]
    np.testing.assert_allclose(sharded, single, rtol=2e-4, atol=2e-5)
