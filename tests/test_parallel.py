"""Multi-device tests on the 8-virtual-CPU-device mesh (conftest sets
XLA_FLAGS) — the SURVEY §4.1 substrate: single host, simulated chips.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.models.zoo import getKerasApplicationModel, getModelFunction
from sparkdl_tpu.parallel import (
    MeshSpec,
    ShardedBatchRunner,
    create_train_state,
    make_eval_step,
    make_mesh,
    make_train_step,
    param_shardings,
    shard_train_step,
)
from sparkdl_tpu.parallel.checkpoint import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)


def test_mesh_shapes():
    mesh = make_mesh()
    assert mesh.shape["data"] == 8 and mesh.shape["model"] == 1
    mesh2 = make_mesh(MeshSpec(data=-1, model=2))
    assert mesh2.shape["data"] == 4 and mesh2.shape["model"] == 2
    with pytest.raises(ValueError):
        MeshSpec(data=3, model=2).resolve(8)


def test_collective_launch_lock_scoping():
    """Multi-device mesh programs can carry collectives, so concurrent
    in-process launchers must share ONE launch lock (interleaved
    per-device enqueues from two threads deadlock the all-reduce —
    the hang test_fit_multiple_parallel_trials used to hit); no mesh
    or a 1-device mesh needs no lock at all. Since the obs PR the
    multi-device case returns the instrumented wrapper around THE
    process lock (parallel/mesh.py::_CollectiveLaunch) — entering it
    must still hold the real lock."""
    from sparkdl_tpu.parallel import mesh as mesh_mod
    from sparkdl_tpu.parallel.mesh import collective_launch

    multi = collective_launch(make_mesh())
    # one process-wide instrumented lock, not one per call
    assert multi is mesh_mod._COLLECTIVE_LAUNCH
    assert collective_launch(make_mesh()) is multi
    single = collective_launch(
        make_mesh(devices=jax.devices()[:1]))
    assert single is not multi
    none = collective_launch(None)
    with none:
        # the 1-device/no-mesh paths never touch the launch lock
        assert not mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()
    with single:
        assert not mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()
    # entering the wrapper takes the REAL process lock; it is
    # reusable across steps and releases on exit
    with multi:
        assert mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()
    assert not mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()
    with multi:
        assert mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()
    assert not mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()


def test_sharded_runner_pickle_keeps_model_axis():
    """Shipping a model-parallel runner must preserve the parallelism
    LAYOUT: devices are re-derived on the receiving host, but the
    model-axis width travels (a silent collapse to pure DP would
    recompile the program against the wrong sharding)."""
    import cloudpickle as cp

    from sparkdl_tpu.graph.function import ModelFunction

    mf = ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                  input_shape=(4,))
    r = ShardedBatchRunner(mf, mesh=make_mesh(MeshSpec(data=-1, model=2)),
                           batch_size=1)
    r2 = cp.loads(cp.dumps(r))
    assert r2.mesh.shape["model"] == 2
    assert r2.mesh.shape["data"] == 4
    n = r2.preferred_chunk
    x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
    np.testing.assert_allclose(r2.run({"input": x})["output"], x * 2)


def test_param_shardings_model_axis():
    mesh = make_mesh(MeshSpec(data=-1, model=2))
    params = {"w": jnp.zeros((6, 4)), "b": jnp.zeros((3,)),
              "scalar": jnp.zeros(())}
    sh = param_shardings(params, mesh)
    assert sh["w"].spec == jax.sharding.PartitionSpec("model", None)
    assert sh["b"].spec == jax.sharding.PartitionSpec()
    assert sh["scalar"].spec == jax.sharding.PartitionSpec()


class TestShardedInference:

    def test_matches_single_device(self):
        mesh = make_mesh()
        mf = getModelFunction("TestNet", featurize=True)
        runner = ShardedBatchRunner(mf, mesh, batch_size=4)
        rng = np.random.default_rng(0)
        x = rng.integers(0, 255, size=(70, 32, 32, 3), dtype=np.uint8)
        out = runner.run({"image": x})["features"]
        assert out.shape == (70, 16)
        ref = np.asarray(mf({"image": x[:70]})["features"])
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        assert runner.metrics.rows == 70

    # global batches of 4 over four virtual devices: partitions that
    # are a multiple of it, not a multiple, one chunk, fewer chunks
    # than the window, empty
    @pytest.mark.parametrize("sizes", [
        [16, 16, 16], [14, 9, 21], [4, 4, 4, 4], [3, 2, 1], [16, 0, 16]])
    @pytest.mark.parametrize("max_inflight", [1, 2, 3])
    def test_window_carried_across_runs_on_four_devices(self, sizes,
                                                        max_inflight):
        """``run(inputs, upcoming=...)`` on the mesh is
        ``dispatch_chunks``' carry too (runtime/runner.py::
        BoundaryCarry): same rows with and without the hand-off."""
        mf = ModelFunction.fromSingle(lambda x: x * 2.0 + 1.0, None,
                                      input_shape=(3,))
        mesh = make_mesh(devices=jax.devices()[:4])
        rng = np.random.default_rng(5)
        parts = [{"input": rng.normal(size=(n, 3)).astype(np.float32)}
                 for n in sizes]
        cold = ShardedBatchRunner(mf, mesh, batch_size=1,
                                  max_inflight=max_inflight)
        warm = ShardedBatchRunner(mf, mesh, batch_size=1,
                                  max_inflight=max_inflight)
        for i, p in enumerate(parts):
            nxt = parts[i + 1] if i + 1 < len(parts) else None
            a = cold.run(p)["output"]
            b = warm.run(p, upcoming=nxt)["output"]
            np.testing.assert_array_equal(a, b)
            np.testing.assert_allclose(b, p["input"] * 2.0 + 1.0,
                                       rtol=1e-6, atol=1e-6)
        m = warm.metrics
        assert m.rows == sum(sizes) == cold.metrics.rows
        assert m.batches == cold.metrics.batches
        device_runs = sum(1 for n in sizes if n)
        assert m.boundary_carried + m.boundary_cold == device_runs - 1
        if 0 not in sizes:
            assert (m.boundary_carried, m.boundary_cold) == \
                (device_runs - 1, 0)
        assert cold.metrics.boundary_cold == device_runs - 1
        assert warm._carry.in_flight == 0

    def test_sharded_span_order_without_and_with_upcoming(self):
        """Without ``upcoming`` the dispatch/readback order is the
        parent's (recorded from commit b5a10c7); with it, the next
        run's first global batches go in as this run's last come out."""
        from sparkdl_tpu.obs import tracer
        mf = ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                      input_shape=(3,))
        mesh = make_mesh(devices=jax.devices()[:4])
        r = ShardedBatchRunner(mf, mesh, batch_size=1)
        x = np.arange(42, dtype=np.float32).reshape(14, 3)
        y = x[::-1].copy()
        r.run({"input": x})

        def letters(spans):
            out = []
            for s in spans:
                if s.name == "dispatch":
                    out.append("n" if s.attrs.get("whose") == "next"
                               else "d")
                elif s.name == "device_get":
                    out.append("g")
                elif s.name == "runner.run_sharded":
                    out.append("|")
            return "".join(out)
        tr = tracer()
        tr.arm()
        tr.clear()
        try:
            r.run({"input": x})
            r.run({"input": x[:5]})
            plain = letters(tr.spans())
            tr.clear()
            r.run({"input": x}, upcoming={"input": y})
            out = r.run({"input": y})["output"]
            carried = letters(tr.spans())
        finally:
            tr.arm_from_env()
            tr.clear()
        assert plain == "dddgdggg|ddgg|"
        assert carried == "dddgdgngng|dgdggg|"
        np.testing.assert_allclose(out, y * 2.0)

    def test_rejects_host_backend(self):
        mf = ModelFunction(lambda p, d: d, backend="host",
                           input_signature={"x": ((2,), np.float32)})
        with pytest.raises(ValueError, match="jax backend"):
            ShardedBatchRunner(mf)

    def test_max_inflight_validated_like_batch_runner(self):
        """The sharded runner shares BatchRunner's window contract: a
        negative depth raises, 0 is the zero-length queue, the choice
        is introspectable, and the removed knobs are gone."""
        mf = getModelFunction("TestNet", featurize=True)
        with pytest.raises(ValueError, match="max_inflight"):
            ShardedBatchRunner(mf, max_inflight=-1)
        assert ShardedBatchRunner(mf, max_inflight=0).max_inflight == 0
        assert ShardedBatchRunner(mf).max_inflight == 2
        with pytest.raises(TypeError):
            ShardedBatchRunner(mf, strategy="deferred")

    def test_aligned_is_zero_copy_and_the_tail_stages_its_rows(self):
        """Exact parity with the unsharded reference for aligned,
        tail-padded, and N=0 inputs — and a batch-ALIGNED contiguous
        run reports ZERO bytes staged/copied (the read-only input pins
        that nothing writes it), while the tail stages exactly the
        tail rows."""
        mesh = make_mesh()
        mf = getModelFunction("TestNet", featurize=True)
        runner = ShardedBatchRunner(mf, mesh, batch_size=4)
        gb = 4 * mesh.shape["data"]  # 32-row global batches
        rng = np.random.default_rng(6)

        x = rng.integers(0, 255, size=(2 * gb, 32, 32, 3),
                         dtype=np.uint8)
        x.setflags(write=False)
        out = runner.run({"image": x})["features"]
        ref = np.asarray(mf({"image": x})["features"])
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        assert runner.metrics.bytes_staged == 0
        assert runner.metrics.bytes_copied == 0

        y = rng.integers(0, 255, size=(2 * gb + 6, 32, 32, 3),
                         dtype=np.uint8)
        y.setflags(write=False)
        out = runner.run({"image": y})["features"]
        ref = np.asarray(mf({"image": y})["features"])
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        assert runner.metrics.bytes_staged == y[2 * gb:].nbytes
        assert runner.metrics.bytes_copied == 0

        empty = runner.run(
            {"image": np.zeros((0, 32, 32, 3), np.uint8)})
        assert empty["features"].shape[0] == 0

    def test_sharded_all_depths_identical(self):
        """Every depth of the window agrees exactly through the
        sharded runner (slab-output parity pin)."""
        mesh = make_mesh()
        mf = getModelFunction("TestNet", featurize=True)
        rng = np.random.default_rng(8)
        x = rng.integers(0, 255, size=(70, 32, 32, 3), dtype=np.uint8)
        expected = None
        for depth in (0, 1, 2, 8):
            r = ShardedBatchRunner(mf, mesh, batch_size=4,
                                   max_inflight=depth)
            out = r.run({"image": x})["features"]
            assert out.shape == (70, 16), depth
            if expected is None:
                expected = out
            else:
                np.testing.assert_array_equal(out, expected)


class TestDPTraining:

    def _setup(self, mesh):
        spec = getKerasApplicationModel("TestNet")
        module = spec.module_fn()
        x = jnp.zeros((1, 32, 32, 3), jnp.uint8)
        variables = module.init(jax.random.PRNGKey(0), spec.preprocess(x))
        state = create_train_state(module, variables,
                                   optax.sgd(1e-2, momentum=0.9))
        step = make_train_step(module, spec.preprocess, spec.num_classes)
        return spec, module, state, step

    def test_loss_decreases_and_stats_update(self):
        mesh = make_mesh()
        spec, module, state, step = self._setup(mesh)
        jitted, state = shard_train_step(step, mesh, state)
        rng = np.random.default_rng(1)
        batch = {
            "image": jnp.asarray(rng.integers(
                0, 255, size=(16, 32, 32, 3), dtype=np.uint8)),
            "label": jnp.asarray(rng.integers(0, 10, size=(16,))),
        }
        first = None
        for _ in range(8):
            state, metrics = jitted(state, batch)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first
        assert int(state.step) == 8

    def test_dp_matches_single_device_step(self):
        """One sharded DP step == the same step unsharded (grads psum
        over the data axis must be numerically equivalent)."""
        mesh = make_mesh()
        spec, module, state0, step = self._setup(mesh)
        rng = np.random.default_rng(2)
        batch = {
            "image": jnp.asarray(rng.integers(
                0, 255, size=(16, 32, 32, 3), dtype=np.uint8)),
            "label": jnp.asarray(rng.integers(0, 10, size=(16,))),
        }
        ref_state, ref_metrics = jax.jit(step)(state0, batch)

        jitted, sharded = shard_train_step(step, mesh, state0)
        new_state, metrics = jitted(sharded, batch)
        np.testing.assert_allclose(float(metrics["loss"]),
                                   float(ref_metrics["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(ref_state.params),
                        jax.tree.leaves(new_state.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_model_axis_sharding_compiles(self):
        mesh = make_mesh(MeshSpec(data=-1, model=2))
        spec, module, state, step = self._setup(mesh)
        jitted, state = shard_train_step(step, mesh, state,
                                         shard_model_axis=True)
        rng = np.random.default_rng(3)
        batch = {
            "image": jnp.asarray(rng.integers(
                0, 255, size=(8, 32, 32, 3), dtype=np.uint8)),
            "label": jnp.asarray(rng.integers(0, 10, size=(8,))),
        }
        state, metrics = jitted(state, batch)
        assert np.isfinite(float(metrics["loss"]))

    def test_eval_step(self):
        mesh = make_mesh()
        spec, module, state, _ = self._setup(mesh)
        ev = jax.jit(make_eval_step(module, spec.preprocess,
                                    spec.num_classes))
        rng = np.random.default_rng(4)
        batch = {
            "image": jnp.asarray(rng.integers(
                0, 255, size=(8, 32, 32, 3), dtype=np.uint8)),
            "label": jnp.asarray(rng.integers(0, 10, size=(8,))),
        }
        m = ev(state, batch)
        assert 0.0 <= float(m["accuracy"]) <= 1.0


class TestCheckpoint:

    def test_save_restore_roundtrip(self, tmp_path):
        spec = getKerasApplicationModel("TestNet")
        module = spec.module_fn()
        x = jnp.zeros((1, 32, 32, 3), jnp.uint8)
        variables = module.init(jax.random.PRNGKey(0), spec.preprocess(x))
        state = create_train_state(module, variables, optax.adam(1e-3))
        step = make_train_step(module, spec.preprocess, spec.num_classes)
        rng = np.random.default_rng(5)
        batch = {
            "image": jnp.asarray(rng.integers(
                0, 255, size=(4, 32, 32, 3), dtype=np.uint8)),
            "label": jnp.asarray(rng.integers(0, 10, size=(4,))),
        }
        state, _ = jax.jit(step)(state, batch)
        ckdir = str(tmp_path / "ck")
        save_checkpoint(ckdir, state, step=1)
        assert latest_step(ckdir) == 1

        fresh = create_train_state(module, variables, optax.adam(1e-3))
        restored = restore_checkpoint(ckdir, fresh)
        assert int(restored.step) == 1
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(restored.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))


class TestAgreeResumeStep:
    """Single-process simulation of the multi-host resume-step descent:
    a scripted agree function plays the global-min rounds of a 2-host
    cluster, asserting each host proposes the right values and both
    converge on max(intersection) with the same collective count."""

    @staticmethod
    def _simulate(hosts):
        """hosts: list of (local_best, available). Runs every host's
        agree_resume_step in lockstep with a real cross-host min."""
        from sparkdl_tpu.parallel.distributed import agree_resume_step

        proposals = [[] for _ in hosts]
        results = [None] * len(hosts)

        # threads: each host runs the real function; a barrier computes
        # the min per round
        import threading
        n = len(hosts)
        lock = threading.Condition()
        round_vals: dict = {}

        def agree_factory(i):
            my_round = [0]

            def agree(value):
                r = my_round[0]
                my_round[0] += 1
                with lock:
                    round_vals.setdefault(r, {})[i] = int(value)
                    lock.notify_all()
                    while len(round_vals[r]) < n:
                        lock.wait(timeout=10)
                    proposals[i].append(int(value))
                    return min(round_vals[r].values())
            return agree

        threads = []
        for i, (best, avail) in enumerate(hosts):
            def run(i=i, best=best, avail=avail):
                results[i] = agree_resume_step(best, avail,
                                               _agree=agree_factory(i))
            t = threading.Thread(target=run)
            threads.append(t)
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive(), "agreement deadlocked"
        return results, proposals

    def test_diverged_views_find_common_step(self):
        # host A holds {1,3} (step-2 save failed), host B holds {1,2}
        # (crashed mid-save of 3): the newest COMMON step is 1
        results, proposals = self._simulate([(3, [1, 3]), (2, [1, 2])])
        assert results == [1, 1]
        # rounds: bests (3,2)->2; best<=2: (1,2)->1; best<=1: (1,1)->1
        assert proposals[0] == [3, 1, 1]
        assert proposals[1] == [2, 2, 1]

    def test_identical_views_resume_newest(self):
        results, _ = self._simulate([(4, [2, 3, 4]), (4, [2, 3, 4])])
        assert results == [4, 4]

    def test_one_host_empty_starts_fresh(self):
        results, _ = self._simulate([(3, [1, 2, 3]), (0, [])])
        assert results == [0, 0]

    def test_single_process_identity(self):
        from sparkdl_tpu.parallel.distributed import (
            agree_min,
            agree_resume_step,
        )
        assert agree_min(7) == 7  # process_count == 1 → identity
        assert agree_resume_step(5, [3, 5]) == 5
        assert agree_resume_step(0, []) == 0
