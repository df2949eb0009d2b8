"""BatchRunner tests (L1: static-shape chunking, padding, async gather)."""

import numpy as np
import pytest

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.runtime.runner import BatchRunner, RunnerMetrics


def _double_fn():
    return ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                    input_shape=(3,))


class TestBatchRunner:
    def test_exact_multiple(self):
        r = BatchRunner(_double_fn(), batch_size=4)
        x = np.arange(24, dtype=np.float32).reshape(8, 3)
        out = r.run({"input": x})["output"]
        np.testing.assert_allclose(out, x * 2)

    def test_padding_last_chunk(self):
        r = BatchRunner(_double_fn(), batch_size=4)
        x = np.arange(21, dtype=np.float32).reshape(7, 3)
        out = r.run({"input": x})["output"]
        assert out.shape == (7, 3)
        np.testing.assert_allclose(out, x * 2)

    def test_smaller_than_batch(self):
        r = BatchRunner(_double_fn(), batch_size=64)
        x = np.ones((2, 3), np.float32)
        np.testing.assert_allclose(r.run({"input": x})["output"], 2.0)

    def test_empty_input(self):
        r = BatchRunner(_double_fn(), batch_size=4)
        out = r.run({"input": np.zeros((0, 3), np.float32)})
        assert out["output"].shape == (0, 3)

    def test_metrics(self):
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), batch_size=4, metrics=m)
        r.run({"input": np.zeros((10, 3), np.float32)})
        assert m.rows == 10
        assert m.batches == 3
        assert m.seconds > 0
        assert m.rows_per_second > 0

    def test_row_count_mismatch(self):
        def two_in(params, inputs):
            return {"out": inputs["a"] + inputs["b"]}
        mf = ModelFunction(two_in, None,
                           {"a": ((2,), np.float32),
                            "b": ((2,), np.float32)})
        r = BatchRunner(mf, batch_size=4)
        with pytest.raises(ValueError, match="rows"):
            r.run({"a": np.zeros((3, 2), np.float32),
                   "b": np.zeros((4, 2), np.float32)})

    def test_signature_validation_names_both_sides(self):
        """A missing/mis-shaped input raises HERE with both names —
        not a bare KeyError or a flax shape error from inside the
        traced program (review r5 probe)."""
        r = BatchRunner(_double_fn(), batch_size=4)
        with pytest.raises(ValueError, match="missing from"):
            r.run({"wrong": np.zeros((4, 3), np.float32)})
        with pytest.raises(ValueError, match="expects"):
            r.run({"input": np.zeros((4, 7), np.float32)})
        # extra keys are tolerated (the model ignores them)
        out = r.run({"input": np.ones((2, 3), np.float32),
                     "extra": np.zeros((2, 1), np.float32)})
        np.testing.assert_allclose(out["output"], 2.0)
        # zero-row inputs keep their empty-batch tolerance even when
        # FLAT (empty variable-list columns arrive as (0,))
        empty = r.run({"input": np.zeros((0,), np.float32)})
        assert empty["output"].shape == (0, 3)
        # jax models with scalar rows () ARE enforced ((4,3) into a
        # scalar-input model must not sail into an XLA error)
        scal = BatchRunner(ModelFunction.fromSingle(
            lambda x: x * 2.0, None, input_shape=()), batch_size=4)
        with pytest.raises(ValueError, match="expects"):
            scal.run({"input": np.zeros((4, 3), np.float32)})

    def test_deserialize_garbage_raises_clearly(self):
        from sparkdl_tpu.graph.ingest import ModelIngest

        with pytest.raises(ValueError, match="StableHLO"):
            ModelIngest.fromExport(b"definitely not an export")

    def test_bad_batch_size(self):
        with pytest.raises(ValueError):
            BatchRunner(_double_fn(), batch_size=0)

    def test_strategy_resolution(self, monkeypatch):
        from sparkdl_tpu.runtime.runner import resolve_strategy

        # isolate from the documented env override: a developer running
        # the suite with SPARKDL_TPU_RUNNER_STRATEGY exported must not
        # see spurious failures here
        monkeypatch.delenv("SPARKDL_TPU_RUNNER_STRATEGY", raising=False)
        assert resolve_strategy("immediate", None) == ("immediate", 0)
        assert resolve_strategy("deferred", 5) == ("deferred", 5)
        from sparkdl_tpu.runtime.runner import MAX_INFLIGHT_HOST_ASYNC
        assert resolve_strategy("host_async", None) == \
            ("host_async", MAX_INFLIGHT_HOST_ASYNC)
        assert resolve_strategy("host_async", 3) == ("host_async", 3)
        # the marker-free default: deferred, double-buffered
        from sparkdl_tpu.runtime.runner import MAX_INFLIGHT_BATCHES
        assert resolve_strategy(None, None) == \
            ("deferred", MAX_INFLIGHT_BATCHES)
        # an explicit queue depth means the caller wants a queue: it
        # keeps deferred at that depth, and 0 means immediate
        assert resolve_strategy(None, 8) == ("deferred", 8)
        assert resolve_strategy(None, 0) == ("immediate", 0)
        # contradictions and typos are loud
        with pytest.raises(ValueError, match="contradicts"):
            resolve_strategy("immediate", 8)
        with pytest.raises(ValueError, match="immediate"):
            resolve_strategy("immedaite", None)
        r = BatchRunner(_double_fn(), strategy="immediate")
        assert r.strategy == "immediate" and r.max_inflight == 0

    def test_all_strategies_produce_identical_outputs(self):
        """immediate / deferred / host_async / prefetch are pure
        dispatch policies — same results, same order, for aligned,
        tail-padded, and N=0 inputs (the slab-output parity pin)."""
        cases = {
            "tail": np.arange(22 * 3, dtype=np.float32).reshape(22, 3),
            "aligned": np.arange(8 * 3, dtype=np.float32).reshape(8, 3),
            "empty": np.zeros((0, 3), np.float32),
        }
        for name, x in cases.items():
            expected = None
            for strategy in ("immediate", "deferred", "host_async",
                             "prefetch"):
                r = BatchRunner(_double_fn(), batch_size=4,
                                strategy=strategy)
                out = r.run({"input": x})["output"]
                assert out.shape == x.shape, (name, strategy)
                if expected is None:
                    expected = out
                else:
                    np.testing.assert_array_equal(out, expected)
            np.testing.assert_allclose(expected, x * 2.0)

    def test_host_backend(self):
        def host_apply(params, inputs):
            return {"y": np.asarray(inputs["x"]) + 1.0}
        mf = ModelFunction(host_apply, None, {"x": ((3,), np.float32)},
                           output_names=["y"], backend="host")
        r = BatchRunner(mf, batch_size=4)
        x = np.zeros((6, 3), np.float32)
        np.testing.assert_allclose(r.run({"x": x})["y"], 1.0)

    def test_device_params_cached_and_invalidated(self):
        """Params transfer to the device once per params object and the
        cache invalidates when .params is reassigned (regression: a
        runner-level cache served stale weights after reassignment)."""
        mf = ModelFunction.fromSingle(
            lambda p, x: x * p["scale"], {"scale": np.float32(2.0)},
            input_shape=(2,))
        r = BatchRunner(mf, batch_size=4)
        x = np.ones((3, 2), np.float32)
        np.testing.assert_allclose(r.run({"input": x})["output"], 2.0)
        assert mf.device_params() is mf.device_params()  # cached

        mf.params = {"scale": np.float32(5.0)}
        np.testing.assert_allclose(r.run({"input": x})["output"], 5.0)

    def test_aligned_run_is_zero_copy(self):
        """The zero-copy hot path pinned by counters: a batch-aligned
        contiguous input ships as plain views — RunnerMetrics reports
        ZERO bytes staged and ZERO bytes copied. The input is marked
        read-only so any staging write into it would raise."""
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), batch_size=4, metrics=m)
        x = np.arange(24, dtype=np.float32).reshape(8, 3)
        x.setflags(write=False)
        np.testing.assert_allclose(r.run({"input": x})["output"], x * 2)
        assert m.bytes_staged == 0 and m.bytes_copied == 0, m
        # a tail-padded run stages EXACTLY the tail rows, nothing more
        y = np.arange(30, dtype=np.float32).reshape(10, 3)
        y.setflags(write=False)
        np.testing.assert_allclose(r.run({"input": y})["output"], y * 2)
        assert m.bytes_staged == y[8:].nbytes, m
        assert m.bytes_copied == 0, m

    def test_non_contiguous_input_counts_copies(self):
        """Non-contiguous rows (e.g. a strided column view) can't ship
        as views — they are copied, and the copy is COUNTED: the
        counters must not claim zero-copy for a path that copies."""
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), batch_size=4, metrics=m)
        x = np.arange(8 * 6, dtype=np.float32).reshape(8, 6)[:, ::2]
        assert not x.flags.c_contiguous
        np.testing.assert_allclose(r.run({"input": x})["output"], x * 2)
        assert m.bytes_copied == x.nbytes, m
        assert m.bytes_staged == 0, m

    def test_iter_padded_chunks_views_and_persistent_staging(self):
        """Full chunks are VIEWS of the input (zero host copies); the
        tail stages through ONE persistent buffer reused across calls,
        with the pad region re-zeroed when the next tail is shorter."""
        from sparkdl_tpu.runtime.runner import (
            CopyCounters,
            PadStaging,
            iter_padded_chunks,
        )

        x = np.arange(33, dtype=np.float32).reshape(11, 3)
        x.setflags(write=False)
        staging, counters = PadStaging(), CopyCounters()
        chunks = list(iter_padded_chunks({"x": x}, 11, 4,
                                         staging, counters))
        assert [v for v, _ in chunks] == [4, 4, 3]
        assert np.shares_memory(chunks[0][1]["x"], x)
        assert np.shares_memory(chunks[1][1]["x"], x)
        tail = chunks[2][1]["x"]
        assert not np.shares_memory(tail, x)
        assert tail.shape == (4, 3)
        np.testing.assert_array_equal(tail[:3], x[8:])
        np.testing.assert_array_equal(tail[3:], 0.0)
        assert counters.bytes_copied == 0
        assert counters.bytes_staged == x[8:].nbytes
        # second call, shorter tail: SAME buffer object, stale rows
        # from the previous tail re-zeroed
        y = np.ones((6, 3), np.float32)
        c2 = list(iter_padded_chunks({"x": y}, 6, 4, staging,
                                     CopyCounters()))
        assert c2[1][1]["x"] is tail  # persistent buffer reused
        np.testing.assert_array_equal(tail[:2], 1.0)
        np.testing.assert_array_equal(tail[2:], 0.0)

    def test_prefetch_propagates_real_device_put_errors(self,
                                                        monkeypatch):
        """A runtime failure inside device_put must surface, never
        degrade the strategy."""
        import sparkdl_tpu.runtime.runner as rmod

        def broken_put(v, *a, **k):
            raise RuntimeError("device OOM")

        monkeypatch.setattr(rmod.jax, "device_put", broken_put)
        r = BatchRunner(_double_fn(), batch_size=4,
                        strategy="prefetch")
        with pytest.raises(RuntimeError, match="device OOM"):
            r.run({"input": np.zeros((8, 3), np.float32)})

    def test_runner_pickles_without_lock_state(self):
        """Device stage closures holding a runner ship to Spark
        executors — the staging lock/buffers must drop on pickle and
        come back fresh (the RunnerMetrics discipline)."""
        cloudpickle = pytest.importorskip("cloudpickle")

        r = BatchRunner(_double_fn(), batch_size=4)
        x = np.arange(30, dtype=np.float32).reshape(10, 3)
        r.run({"input": x})  # warm staging so there IS state to drop
        r2 = cloudpickle.loads(cloudpickle.dumps(r))
        np.testing.assert_allclose(r2.run({"input": x})["output"],
                                   x * 2.0)

    def test_params_cache_purges_all_placements(self):
        """Reassigning .params purges every cached placement, not just
        the next-accessed key (regression: dead replicated copies held
        device memory)."""
        from sparkdl_tpu.parallel.mesh import make_mesh
        mf = ModelFunction.fromSingle(
            lambda p, x: x * p["s"], {"s": np.float32(2.0)},
            input_shape=(2,))
        mesh = make_mesh()
        mf.device_params()
        mf.replicated_params(mesh)
        assert len(mf._params_cache) == 2
        mf.params = {"s": np.float32(3.0)}
        mf.device_params()   # triggers purge of the stale replicated copy
        assert len(mf._params_cache) == 1
        np.testing.assert_allclose(
            np.asarray(mf.replicated_params(mesh)["s"]), 3.0)
