"""Engine retry + multi-host sharding + graph utils tests (SURVEY §5:
failure detection via task retry; §2.5 DCN host sharding; §2.1 tfx)."""

import threading

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.data.engine import LocalEngine
from sparkdl_tpu.data.frame import DataFrame, Source, Stage
from sparkdl_tpu.graph import utils as tfx
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.parallel import (
    global_mesh,
    host_info,
    host_shard_dataframe,
    host_shard_indices,
    initialize,
)


def _batch(vals):
    return pa.RecordBatch.from_pydict({"x": pa.array(vals)})


class TestEngineRetry:
    def test_transient_failure_retried(self):
        engine = LocalEngine(num_workers=2, max_retries=2)
        fails = {"n": 0}
        lock = threading.Lock()

        def flaky_load():
            with lock:
                fails["n"] += 1
                if fails["n"] == 1:
                    raise IOError("transient read error")
            return _batch([1, 2, 3])

        sources = [Source(flaky_load, 3)]
        out = list(engine.execute(sources, []))
        assert out[0].num_rows == 3
        assert fails["n"] == 2  # one failure + one success

    def test_flaky_stage_retried(self):
        engine = LocalEngine(num_workers=2, max_retries=1)
        attempts = {"n": 0}
        lock = threading.Lock()

        def flaky_stage(batch):
            with lock:
                attempts["n"] += 1
                if attempts["n"] == 1:
                    raise IOError("decode read hiccup")
            return batch

        sources = [Source(lambda: _batch([1]), 1)]
        out = list(engine.execute(sources, [Stage(flaky_stage)]))
        assert out[0].num_rows == 1

    def test_permanent_failure_raises_after_attempts(self):
        engine = LocalEngine(num_workers=1, max_retries=2)
        calls = {"n": 0}

        def always_fails():
            calls["n"] += 1
            raise IOError("disk gone")

        sources = [Source(always_fails, 1)]
        with pytest.raises(IOError, match="disk gone"):
            list(engine.execute(sources, []))
        assert calls["n"] == 3

    def test_zero_retries(self):
        engine = LocalEngine(num_workers=1, max_retries=0)
        calls = {"n": 0}

        def fails():
            calls["n"] += 1
            raise IOError("nope")

        with pytest.raises(IOError, match="nope"):
            list(engine.execute([Source(fails, 1)], []))
        assert calls["n"] == 1

    def test_transient_device_error_retried(self):
        """A PJRT/jax runtime failure mid-partition (e.g. a lost
        device connection) must be retried like an IO error — the
        partition re-runs cleanly from its source."""
        from jax.errors import JaxRuntimeError

        engine = LocalEngine(num_workers=2, max_retries=2)
        attempts = {"n": 0}
        lock = threading.Lock()

        def flaky_device_stage(batch):
            with lock:
                attempts["n"] += 1
                if attempts["n"] == 1:
                    raise JaxRuntimeError(
                        "UNAVAILABLE: connection reset")
            return batch

        out = list(engine.execute(
            [Source(lambda: _batch([1, 2]), 2)],
            [Stage(flaky_device_stage, kind="device")]))
        assert out[0].num_rows == 2
        assert attempts["n"] == 2

    def test_deterministic_jax_status_not_retried(self):
        """A jax error whose status code means 'this will fail the same
        way again' (INVALID_ARGUMENT, a deterministic RESOURCE_EXHAUSTED
        allocation failure) must propagate on FIRST failure — re-running
        a decode-bearing partition 3x before the inevitable error would
        triple time-to-failure and mislabel it transient."""
        from jax.errors import JaxRuntimeError

        for status in ("INVALID_ARGUMENT: operand shapes",
                       "RESOURCE_EXHAUSTED: allocating 40G exceeds HBM",
                       # wrapping layers prefix context; the status
                       # token must still classify as deterministic
                       "Execution failed: INVALID_ARGUMENT: bad dims"):
            engine = LocalEngine(num_workers=1, max_retries=3)
            calls = {"n": 0}

            def stage(batch, status=status):
                calls["n"] += 1
                raise JaxRuntimeError(status)

            with pytest.raises(JaxRuntimeError):
                list(engine.execute([Source(lambda: _batch([1]), 1)],
                                    [Stage(stage, kind="device")]))
            assert calls["n"] == 1, status

    def test_classifier_tolerates_degenerate_messages(self):
        """Empty / whitespace-only jax error messages must classify
        (as non-deterministic), not crash the classifier and mask the
        original device error."""
        from jax.errors import JaxRuntimeError

        from sparkdl_tpu.data.engine import is_deterministic_jax_error

        for msg in ("", "\n", "   ", "\n\nINVALID_ARGUMENT: late"):
            assert is_deterministic_jax_error(JaxRuntimeError(msg)) \
                == ("INVALID_ARGUMENT" in msg)

    def test_custom_retryable_set(self):
        """retryable_exceptions is configurable; an exception outside
        the set propagates on first failure."""
        class Flaky(Exception):
            pass

        engine = LocalEngine(num_workers=1, max_retries=3,
                             retryable_exceptions=(Flaky,))
        calls = {"n": 0}

        def stage(batch):
            calls["n"] += 1
            if calls["n"] == 1:
                raise Flaky("once")
            return batch

        out = list(engine.execute([Source(lambda: _batch([1]), 1)],
                                  [Stage(stage)]))
        assert out[0].num_rows == 1 and calls["n"] == 2

        # OSError is now OUTSIDE the configured set → no retry
        calls2 = {"n": 0}

        def io_fails(batch):
            calls2["n"] += 1
            raise IOError("disk gone")

        with pytest.raises(IOError):
            list(engine.execute([Source(lambda: _batch([1]), 1)],
                                [Stage(io_fails)]))
        assert calls2["n"] == 1

    def test_deterministic_error_not_retried(self):
        engine = LocalEngine(num_workers=1, max_retries=3)
        calls = {"n": 0}

        def bad_stage(batch):
            calls["n"] += 1
            raise KeyError("column 'nope' not in batch")

        with pytest.raises(KeyError, match="nope"):
            list(engine.execute([Source(lambda: _batch([1]), 1)],
                                [Stage(bad_stage)]))
        assert calls["n"] == 1  # no pointless retries of user errors


class TestHostSharding:
    def test_single_process_owns_everything(self):
        initialize()  # no-op single process
        info = host_info()
        assert info.process_count == 1
        assert info.process_index == 0
        assert host_shard_indices(5) == [0, 1, 2, 3, 4]

    def test_initialize_attempts_join_with_explicit_args(self,
                                                         monkeypatch):
        """Explicit multi-process args must reach
        jax.distributed.initialize (regression: the old process_count
        guard initialized the backend itself, making real
        initialization unreachable)."""
        import jax
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        initialize(coordinator_address="10.0.0.1:1234",
                   num_processes=2, process_id=0)
        assert calls == [{"coordinator_address": "10.0.0.1:1234",
                          "num_processes": 2, "process_id": 0}]

    def test_initialize_auto_detect_env(self, monkeypatch):
        """A cluster env marker must trigger an initialize attempt even
        with no args (TPU pod auto-detection path; regression: the old
        all-None early return skipped it)."""
        import jax
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        monkeypatch.setenv("SLURM_JOB_ID", "12345")
        initialize()
        assert len(calls) == 1

    def test_initialize_plain_single_process_noop(self, monkeypatch):
        import jax
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        for v in ("JAX_COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS",
                  "TPU_WORKER_HOSTNAMES", "SLURM_JOB_ID",
                  "OMPI_COMM_WORLD_SIZE"):
            monkeypatch.delenv(v, raising=False)
        initialize()
        assert calls == []

    def test_round_robin_explicit(self):
        assert host_shard_indices(10, process_index=0,
                                  process_count=4) == [0, 4, 8]
        assert host_shard_indices(10, process_index=3,
                                  process_count=4) == [3, 7]
        # every partition owned exactly once
        owned = sorted(sum((host_shard_indices(10, i, 4)
                            for i in range(4)), []))
        assert owned == list(range(10))

    def test_invalid_process(self):
        with pytest.raises(ValueError, match="invalid process"):
            host_shard_indices(4, process_index=4, process_count=4)

    def test_host_shard_dataframe_lazy(self):
        loaded = []

        def make(i):
            def _load():
                loaded.append(i)
                return _batch([i])
            return Source(_load, 1)

        df = DataFrame([make(i) for i in range(6)])
        mine = host_shard_dataframe(df, process_index=1, process_count=3)
        assert mine.num_partitions == 2
        rows = mine.collect_rows()
        assert [r["x"] for r in rows] == [1, 4]
        assert sorted(loaded) == [1, 4]  # other hosts' sources untouched

    def test_global_mesh_shape(self):
        mesh = global_mesh()
        assert mesh.devices.size == 8  # conftest's virtual CPU devices
        assert mesh.axis_names == ("data", "model")


class TestGraphUtils:
    def _mf(self):
        return ModelFunction.fromSingle(
            lambda x: x * 2.0, None, input_shape=(3,),
            input_name="inp", output_name="out", name="m")

    def test_validated_io(self):
        mf = self._mf()
        assert tfx.validated_input(mf, "inp") == "inp"
        assert tfx.validated_output(mf, "out") == "out"
        with pytest.raises(ValueError, match="not in model"):
            tfx.validated_input(mf, "bogus")
        with pytest.raises(ValueError, match="not in model"):
            tfx.validated_output(mf, "bogus")
        with pytest.raises(TypeError, match="ModelFunction"):
            tfx.validated_model("not a model")

    def test_shapes_and_names(self):
        mf = self._mf()
        assert tfx.get_input_shape(mf, "inp") == (3,)
        assert tfx.get_output_shape(mf, "out") == (3,)
        assert tfx.input_names(mf) == ["inp"]
        assert tfx.output_names(mf) == ["out"]

    def test_freeze_roundtrip(self):
        mf = self._mf()
        blob = tfx.strip_and_freeze(mf)
        assert isinstance(blob, bytes) and len(blob) > 0
        back = tfx.load_frozen(blob)
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        np.testing.assert_allclose(
            np.asarray(back({"inp": x})["out"]), x * 2.0)

    def test_fixed_batch_freeze_roundtrip(self):
        """A fixed-batch export must deserialize with working output
        names (regression: the lazy name probe ran the program with
        batch 1, which a fixed-batch export rejects)."""
        mf = self._mf()
        back = tfx.load_frozen(mf.export(batch_size=3))
        assert back.output_names == ["out"]
        # output_signature must come from the exported avals, not an
        # eval_shape probe (which would call the program with batch 1)
        shape, dtype = back.output_signature()["out"]
        assert shape == (3,) and np.dtype(dtype) == np.float32
        assert tfx.get_output_shape(back, "out") == (3,)
        x = np.arange(9, dtype=np.float32).reshape(3, 3)
        np.testing.assert_allclose(
            np.asarray(back({"inp": x})["out"]), x * 2.0)

    def test_select_outputs_prunes(self):
        def two_headed(x):
            return {"a": x + 1.0, "b": x * 3.0}

        mf = ModelFunction(
            lambda p, d: two_headed(d["inp"]), None,
            input_signature={"inp": ((3,), np.dtype(np.float32))},
            output_names=["a", "b"], name="two")
        pruned = tfx.select_outputs(mf, ["b"])
        assert pruned.output_names == ["b"]
        x = np.ones((2, 3), np.float32)
        out = pruned({"inp": x})
        assert set(out) == {"b"}
        np.testing.assert_allclose(np.asarray(out["b"]), x * 3.0)
        with pytest.raises(ValueError, match="not in model"):
            tfx.select_outputs(mf, ["bogus"])
        with pytest.raises(ValueError, match="at least one"):
            tfx.select_outputs(mf, [])

    def test_with_preprocessor_fuses(self):
        mf = self._mf()
        pre = tfx.with_preprocessor(
            mf, lambda ins: {"inp": ins["inp"] + 10.0})
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        np.testing.assert_allclose(
            np.asarray(pre({"inp": x})["out"]), (x + 10.0) * 2.0)
        # composed program still exports to StableHLO (deploy form)
        blob = tfx.strip_and_freeze(pre)
        back = tfx.load_frozen(blob)
        np.testing.assert_allclose(
            np.asarray(back({"inp": x})["out"]), (x + 10.0) * 2.0)

    def test_with_postprocessor_infers_names(self):
        mf = self._mf()
        post = tfx.with_postprocessor(
            mf, lambda outs: {"flat": outs["out"].reshape(
                outs["out"].shape[0], -1).sum(axis=1)})
        assert post.output_names == ["flat"]
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        np.testing.assert_allclose(
            np.asarray(post({"inp": x})["flat"]), (x * 2.0).sum(axis=1))


# ---------------------------------------------------------------------------
# ISSUE 11: the resilience layer — classification, fault harness, retry policy,
# circuit breaking, serve re-dispatch, SLO-aware priority shedding.

import time

from sparkdl_tpu import resilience
from sparkdl_tpu.data.frame import Source as _Source, Stage as _Stage
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry
from sparkdl_tpu.obs.slo import slo_tracker
from sparkdl_tpu.resilience import faults as rfaults
from sparkdl_tpu.resilience.errors import (
    PermanentError,
    TransientError,
    classify,
    is_transient,
)
from sparkdl_tpu.resilience.faults import (
    FaultSpecError,
    InjectedFault,
    InjectedPermanentFault,
)
from sparkdl_tpu.resilience.policy import (
    CircuitBreaker,
    CircuitOpen,
    RetryBudgetExhausted,
    RetryPolicy,
)
from sparkdl_tpu.serve import (
    ModelServer,
    Request,
    RequestQueue,
    ServeConfig,
    ServerOverloaded,
    ShedForPriority,
)


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test in this file starts and ends with the harness
    disarmed — injection is per-test, never ambient."""
    rfaults.disarm()
    yield
    rfaults.disarm()


def _echo_mf(row=(2,), factor=2.0):
    def apply(params, inputs):
        return {"y": np.asarray(inputs["x"], np.float32) * factor}
    return ModelFunction(apply, None, {"x": (tuple(row), np.float32)},
                         output_names=["y"], backend="host")


def _counter(name):
    return default_registry().snapshot().get(name, 0.0)


class TestErrorClassification:
    def test_typed_markers_win(self):
        class Weird(OSError, PermanentError):
            pass
        assert is_transient(TransientError("x"))
        assert not is_transient(PermanentError("x"))
        # PermanentError beats the otherwise-retryable OSError family
        assert not is_transient(Weird("x"))

    def test_heuristic_families(self):
        from jax.errors import JaxRuntimeError
        assert classify(IOError("disk")) == "transient"
        assert classify(KeyError("col")) == "permanent"
        assert classify(JaxRuntimeError(
            "UNAVAILABLE: connection reset")) == "transient"
        assert classify(JaxRuntimeError(
            "INVALID_ARGUMENT: bad dims")) == "permanent"

    def test_injected_faults_classify(self):
        assert classify(InjectedFault("drill")) == "transient"
        assert classify(InjectedPermanentFault("drill")) == "permanent"

    def test_engine_reexports_survive_the_move(self):
        # the classification moved to resilience/; the engine names are API
        from sparkdl_tpu.data.engine import (
            default_retryable_exceptions as engine_dre,
        )
        from sparkdl_tpu.resilience.errors import (
            default_retryable_exceptions as res_dre,
        )
        assert engine_dre() == res_dre()
        assert TransientError in engine_dre()


class TestFaultHarness:
    def test_inject_validates_loudly(self):
        with pytest.raises(FaultSpecError, match="unknown fault site"):
            resilience.inject("nope.site")
        with pytest.raises(FaultSpecError, match="unknown fault kind"):
            resilience.inject("serve.dispatch", kind="flaky")
        with pytest.raises(FaultSpecError, match="rate"):
            resilience.inject("serve.dispatch", rate=0.0)
        with pytest.raises(FaultSpecError, match="rate"):
            resilience.inject("serve.dispatch", rate=1.5)

    def test_deterministic_sequence_per_seed(self):
        def pattern():
            fired = []
            for _ in range(24):
                try:
                    rfaults.maybe_fail("model.fetch")
                    fired.append(False)
                except InjectedFault:
                    fired.append(True)
            return fired

        resilience.inject("model.fetch", rate=0.5, seed=3)
        first = pattern()
        rfaults.disarm()
        resilience.inject("model.fetch", rate=0.5, seed=3)
        assert pattern() == first
        assert any(first) and not all(first)

    def test_registry_family_counts(self):
        before_total = _counter("faults.injected")
        before_site = _counter("faults.model.fetch.injected")
        resilience.inject("model.fetch", rate=1.0)
        for _ in range(3):
            with pytest.raises(InjectedFault):
                rfaults.maybe_fail("model.fetch")
        assert _counter("faults.injected") == before_total + 3
        assert _counter("faults.model.fetch.injected") == \
            before_site + 3
        st = rfaults.state()
        assert st["armed"] and \
            st["sites"]["model.fetch"]["injected"] == 3

    def test_env_spec_arms(self, monkeypatch):
        monkeypatch.setenv(
            "SPARKDL_TPU_FAULTS",
            "serve.dispatch:transient:0.25:7,model.fetch:permanent:1.0")
        assert rfaults.arm_from_env()
        st = rfaults.state()
        assert st["sites"]["serve.dispatch"] == {
            "kind": "transient", "rate": 0.25, "seed": 7,
            "checks": 0, "injected": 0}
        assert st["sites"]["model.fetch"]["kind"] == "permanent"

    def test_env_typo_degrades_disarmed(self, monkeypatch, caplog):
        for bad in ("serve.dispatch", "serve.dispatch:transient:2.0",
                    "bogus.site:transient:0.5",
                    "serve.dispatch:transient:zero"):
            monkeypatch.setenv("SPARKDL_TPU_FAULTS", bad)
            with caplog.at_level("WARNING",
                                 logger="sparkdl_tpu.resilience.faults"):
                assert not rfaults.arm_from_env(), bad
            assert not rfaults.state()["armed"], bad
        assert any("not a valid fault spec" in r.getMessage()
                   for r in caplog.records)

    def test_disarmed_overhead_every_site(self):
        """The acceptance bound: a disarmed site check rides the
        tracer's <10 µs shared no-op regime (min over repeats —
        noise only ever adds time)."""
        n = 4_000
        for site in rfaults.SITES:
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(n):
                    rfaults.maybe_fail(site)
                best = min(best, (time.perf_counter() - t0) / n)
            assert best < 10e-6, \
                f"disarmed {site} costs {best * 1e6:.2f} µs"

    def test_partial_arm_keeps_other_sites_noop(self):
        resilience.inject("model.fetch", rate=1.0)
        # an armed plan must not start firing at un-armed sites
        rfaults.maybe_fail("serve.dispatch")
        rfaults.maybe_fail("engine.source_load")
        rfaults.disarm("model.fetch")
        assert not rfaults.state()["armed"]


class TestFaultSitesThreaded:
    """Each named site actually fires from its real hot path."""

    def test_engine_source_load_retries_injected_transient(self):
        # seed 1, rate 0.5: first draw fires, second passes — the
        # partition retry recovers and the data is intact
        resilience.inject("engine.source_load", rate=0.5, seed=1)
        before = _counter("engine.retries")
        engine = LocalEngine(num_workers=1, max_retries=2)
        out = list(engine.execute([Source(lambda: _batch([1, 2]), 2)],
                                  []))
        assert out[0].num_rows == 2
        assert _counter("engine.retries") == before + 1
        assert rfaults.state()["sites"]["engine.source_load"][
            "injected"] == 1

    def test_engine_stage_apply_permanent_fails_fast(self):
        resilience.inject("engine.stage_apply", kind="permanent",
                          rate=1.0)
        engine = LocalEngine(num_workers=1, max_retries=3)
        with pytest.raises(InjectedPermanentFault):
            list(engine.execute([Source(lambda: _batch([1]), 1)],
                                [_Stage(lambda b: b)]))
        # permanent = classified non-retryable: exactly ONE attempt
        assert rfaults.state()["sites"]["engine.stage_apply"][
            "checks"] == 1

    def test_ship_sites_fire_from_dispatch_chunks(self):
        from sparkdl_tpu.runtime.runner import BatchRunner
        mf = ModelFunction.fromSingle(
            lambda x: x * 2.0, None, input_shape=(3,),
            input_name="x", output_name="y", name="m")
        runner = BatchRunner(mf, batch_size=4)
        x = np.ones((8, 3), np.float32)
        for site in ("ship.device_put", "ship.drain"):
            rfaults.disarm()
            resilience.inject(site, rate=1.0)
            with pytest.raises(InjectedFault):
                runner.run({"x": x})
            assert rfaults.state()["sites"][site]["injected"] >= 1
        rfaults.disarm()
        out = runner.run({"x": x})     # disarmed: clean run after
        np.testing.assert_allclose(out["y"], 2.0)

    def test_collective_launch_site_never_leaks_the_lock(self):
        from sparkdl_tpu.parallel.mesh import (
            _COLLECTIVE_LAUNCH_LOCK,
            collective_launch,
        )
        mesh = global_mesh()
        resilience.inject("collective.launch", rate=1.0)
        with pytest.raises(InjectedFault):
            with collective_launch(mesh):
                pass
        assert not _COLLECTIVE_LAUNCH_LOCK.locked()
        rfaults.disarm()
        with collective_launch(mesh):   # clean entry after the drill
            assert _COLLECTIVE_LAUNCH_LOCK.locked()
        assert not _COLLECTIVE_LAUNCH_LOCK.locked()

    def test_model_fetch_site(self, tmp_path):
        from sparkdl_tpu.models.fetcher import ModelFetcher
        f = ModelFetcher(cache_dir=str(tmp_path))
        params = {"w": np.ones((2,), np.float32)}
        f.put("m.msgpack", params)
        resilience.inject("model.fetch", rate=1.0)
        with pytest.raises(InjectedFault):
            f.get("m.msgpack", params)
        rfaults.disarm()
        back = f.get("m.msgpack", params)
        np.testing.assert_allclose(back["w"], 1.0)


class TestRetryPolicy:
    def test_bounded_attempts_reraise_original(self):
        p = RetryPolicy(attempts=3, base_backoff_s=0.0,
                        sleep=lambda s: None)
        calls = []

        def fails():
            calls.append(1)
            raise InjectedFault("always")

        with pytest.raises(InjectedFault):
            p.call(fails)
        assert len(calls) == 3

    def test_non_retryable_propagates_first(self):
        p = RetryPolicy(attempts=5, base_backoff_s=0.0,
                        sleep=lambda s: None)
        calls = []

        def fails():
            calls.append(1)
            raise KeyError("permanent user error")

        with pytest.raises(KeyError):
            p.call(fails)
        assert len(calls) == 1

    def test_backoff_exponential_capped_deterministic(self):
        p = RetryPolicy(attempts=8, base_backoff_s=0.1,
                        max_backoff_s=0.4, jitter_frac=0.25)
        d1, d2, d3 = (p.backoff_s(a, "k") for a in (1, 2, 3))
        assert 0.1 <= d1 <= 0.125
        assert 0.2 <= d2 <= 0.25
        assert 0.4 <= d3 <= 0.5       # capped at max, jitter on top
        assert p.backoff_s(2, "k") == d2          # deterministic
        assert p.backoff_s(2, "other") != d2      # de-synchronized

    def test_budget_bounds_amplification_typed(self):
        p = RetryPolicy(attempts=2, base_backoff_s=0.0,
                        budget_ratio=0.2, budget_cap=1.0,
                        sleep=lambda s: None)
        before = _counter("resilience.budget_denied")

        def fails():
            raise InjectedFault("dependency down")

        with pytest.raises(InjectedFault):
            p.call(fails)               # spends the one token
        with pytest.raises(RetryBudgetExhausted) as ei:
            p.call(fails)               # bucket empty -> typed refusal
        assert isinstance(ei.value.__cause__, InjectedFault)
        assert isinstance(ei.value, PermanentError)  # outer no-retry
        assert _counter("resilience.budget_denied") == before + 1

    def test_deposits_refill_the_bucket(self):
        p = RetryPolicy(attempts=2, base_backoff_s=0.0,
                        budget_ratio=1.0, budget_cap=1.0,
                        sleep=lambda s: None)
        for _ in range(4):      # ratio 1.0: every call earns a retry
            calls = []

            def flaky():
                calls.append(1)
                if len(calls) == 1:
                    raise InjectedFault("once")
                return "ok"

            assert p.call(flaky) == "ok"

    def test_deadline_blocks_late_retry(self):
        p = RetryPolicy(attempts=5, base_backoff_s=0.2,
                        sleep=lambda s: None)
        calls = []

        def fails():
            calls.append(1)
            raise InjectedFault("x")

        with pytest.raises(InjectedFault):
            p.call(fails, deadline=time.perf_counter() + 0.01)
        assert len(calls) == 1  # backoff 0.2s cannot fit in 10ms

    def test_pickle_round_trip(self):
        import cloudpickle
        import pickle
        p = RetryPolicy(attempts=4, base_backoff_s=0.03, seed=9)
        p2 = pickle.loads(cloudpickle.dumps(p))
        assert p2.attempts == 4
        assert p2.backoff_s(2, "k") == p.backoff_s(2, "k")
        assert p2.call(lambda: 11) == 11


class TestCircuitBreaker:
    def test_transitions(self):
        clock = [0.0]
        cb = CircuitBreaker(failure_threshold=3, reset_timeout_s=5.0,
                            half_open_probes=1,
                            clock=lambda: clock[0])
        assert cb.state == "closed" and cb.allow()
        cb.record_failure(); cb.record_failure()
        assert cb.state == "closed"     # below threshold
        cb.record_success()
        cb.record_failure(); cb.record_failure(); cb.record_failure()
        assert cb.state == "open" and cb.opens == 1
        assert not cb.allow()
        clock[0] = 4.9
        assert not cb.allow()           # still inside the timeout
        clock[0] = 5.1
        assert cb.allow()               # half-open: the one probe
        assert cb.state == "half_open"
        assert not cb.allow()           # probe budget spent
        cb.record_failure()             # probe failed -> open again
        assert cb.state == "open" and cb.opens == 2
        clock[0] = 11.0
        assert cb.allow()
        cb.record_success()
        assert cb.state == "closed" and cb.allow()
        assert cb.state_code == 0

    def test_lost_probe_self_heals_the_half_open_window(self):
        """A half-open probe that dies BEFORE dispatch (rejected at
        the queue, expired, shed, abandoned by shutdown) produces no
        record_* outcome — the breaker must re-open its probe window
        after reset_timeout_s instead of wedging every future submit
        on a long-recovered model."""
        clock = [0.0]
        cb = CircuitBreaker(failure_threshold=1, reset_timeout_s=5.0,
                            half_open_probes=1,
                            clock=lambda: clock[0])
        cb.record_failure()
        clock[0] = 5.1
        assert cb.allow()               # the probe slot
        assert not cb.allow()           # spent; probe then dies silently
        clock[0] = 10.0
        assert not cb.allow()           # probe window not yet stale
        clock[0] = 10.2
        assert cb.allow()               # self-healed: fresh probe
        cb.record_success()
        assert cb.state == "closed"

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker(failure_threshold=0)
        with pytest.raises(ValueError):
            CircuitBreaker(reset_timeout_s=0)
        with pytest.raises(ValueError):
            CircuitBreaker(half_open_probes=0)

    def test_pickle_reanchors_open_timestamp(self):
        import cloudpickle
        import pickle
        cb = CircuitBreaker(failure_threshold=1, reset_timeout_s=60.0)
        cb.record_failure()
        assert cb.state == "open"
        cb2 = pickle.loads(cloudpickle.dumps(cb))
        assert cb2.state == "open"
        assert not cb2.allow()   # waits a FULL timeout in its process


class TestServeResilience:
    def test_injected_soak_zero_lost_zero_duplicated(self):
        """THE acceptance drill: 10% transient faults at the serve
        dispatch site under a concurrent soak — every admitted request
        resolves (success or typed failure), row identity exact, and
        the re-dispatch path demonstrably engaged."""
        import threading as th
        resilience.inject("serve.dispatch", rate=0.1, seed=1234)
        retries_before = _counter("serve.retries")
        server = ModelServer(ServeConfig(
            max_wait_s=0.001, max_queue_rows=4096,
            dispatch_retries=3, retry_base_backoff_s=0.001))
        server.register("drill", _echo_mf(row=(4,)), batch_size=16)
        futures, lock = [], th.Lock()

        def fire(tid):
            for i in range(30):
                val = float(tid * 100 + i)
                f = server.submit(
                    {"x": np.full((8, 4), val, np.float32)})
                with lock:
                    futures.append((val, f))

        workers = [th.Thread(target=fire, args=(t,)) for t in range(4)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        ok = typed = 0
        for val, f in futures:
            try:
                out = f.result(timeout=60)
                # row identity: the value IS the request id — a lost,
                # duplicated, or cross-wired row shows up here
                assert out["y"].shape == (8, 4)
                np.testing.assert_allclose(out["y"], 2.0 * val)
                ok += 1
            except (InjectedFault, RetryBudgetExhausted):
                typed += 1
        server.close()
        assert ok + typed == len(futures) == 120
        assert ok > 0
        assert _counter("serve.retries") > retries_before
        assert rfaults.state()["sites"]["serve.dispatch"][
            "injected"] > 0

    def test_surviving_requests_redispatch_not_whole_batch(self):
        """Two coalesced requests, one dispatch failure: the batch
        re-dispatches and BOTH resolve — the pre-resilience behavior
        (one transient failure fails every coalesced request) is
        gone. Deterministic: seed 1 / rate 0.5 fires on the first
        check only."""
        resilience.inject("serve.dispatch", rate=0.5, seed=1)
        server = ModelServer(ServeConfig(
            max_wait_s=0.05, dispatch_retries=2,
            retry_base_backoff_s=0.001))
        session = server.register("m", _echo_mf(), batch_size=8)
        session._ensure_worker = lambda: None       # hold the queue
        f1 = server.submit({"x": np.full((4, 2), 1.0, np.float32)})
        f2 = server.submit({"x": np.full((4, 2), 2.0, np.float32)})
        del session.__dict__["_ensure_worker"]      # restore + kick
        session._ensure_worker()
        np.testing.assert_allclose(f1.result(timeout=30)["y"], 2.0)
        np.testing.assert_allclose(f2.result(timeout=30)["y"], 4.0)
        assert session.metrics.retries >= 1
        server.close()

    def test_permanent_fault_never_retries(self):
        resilience.inject("serve.dispatch", kind="permanent", rate=1.0)
        server = ModelServer(ServeConfig(
            max_wait_s=0.0, dispatch_retries=3,
            retry_base_backoff_s=0.001))
        fut = server.register("m", _echo_mf(), batch_size=4).submit(
            {"x": np.zeros((2, 2), np.float32)})
        with pytest.raises(InjectedPermanentFault):
            fut.result(timeout=30)
        # exactly one dispatch attempt: permanent = no re-dispatch
        assert rfaults.state()["sites"]["serve.dispatch"]["checks"] == 1
        assert server.metrics.retries == 0
        server.close()

    def test_retry_budget_exhaustion_stays_typed(self):
        resilience.inject("serve.dispatch", rate=1.0)
        server = ModelServer(ServeConfig(
            max_wait_s=0.0, dispatch_retries=3,
            retry_base_backoff_s=0.0005, retry_budget_ratio=0.1,
            circuit_failure_threshold=1000))
        session = server.register("m", _echo_mf(), batch_size=4)
        outcomes = []
        for i in range(8):
            fut = session.submit({"x": np.zeros((2, 2), np.float32)})
            try:
                fut.result(timeout=30)
                outcomes.append("ok")
            except Exception as e:
                outcomes.append(type(e).__name__)
        # the bucket (cap 8, ratio 0.1) drains; refusals are TYPED
        assert "RetryBudgetExhausted" in outcomes, outcomes
        assert set(outcomes) <= {"InjectedFault",
                                 "RetryBudgetExhausted"}, outcomes
        server.close()

    def test_circuit_open_half_open_close(self):
        resilience.inject("serve.dispatch", kind="permanent", rate=1.0)
        server = ModelServer(ServeConfig(
            max_wait_s=0.0, circuit_failure_threshold=2,
            circuit_reset_s=0.15))
        session = server.register("m", _echo_mf(), batch_size=4)
        for _ in range(2):
            with pytest.raises(InjectedPermanentFault):
                session.submit(
                    {"x": np.zeros((2, 2), np.float32)}).result(
                        timeout=30)
        assert session.circuit.state == "open"
        with pytest.raises(CircuitOpen, match="circuit is open"):
            session.submit({"x": np.zeros((2, 2), np.float32)})
        assert session.metrics.circuit_rejections == 1
        # heal the model, wait out the reset, probe through
        rfaults.disarm()
        time.sleep(0.2)
        probe = session.submit({"x": np.ones((2, 2), np.float32)})
        np.testing.assert_allclose(probe.result(timeout=30)["y"], 2.0)
        assert session.circuit.state == "closed"
        server.close()
        snap = default_registry().snapshot()
        assert snap["serve.circuit_state"] == 0.0
        assert snap["serve.circuit_rejections"] >= 1.0

    def test_statusz_carries_circuit_and_resilience(self):
        server = ModelServer(ServeConfig(max_wait_s=0.0))
        server.register("m", _echo_mf(), batch_size=4)
        st = server.telemetry_status()
        assert st["models"]["m"]["circuit"]["state"] == "closed"
        assert st["models"]["m"]["retry"]["attempts"] == 3
        from sparkdl_tpu.obs.flight import recorder
        bundle = recorder().bundle(reason="test")
        assert "faults" in bundle["resilience"]
        assert bundle["resilience"]["circuits"]["m"][
            "state"] == "closed"
        server.close()


class TestPriorityShedding:
    def _req(self, rows, priority, deadline=None):
        return Request({"x": np.zeros((rows, 2), np.float32)}, rows,
                       deadline, priority=priority)

    def test_displacement_lowest_newest_first(self):
        q = RequestQueue()
        p0_old = self._req(4, 0)
        p0_new = self._req(4, 0)
        p1 = self._req(8, 1)
        for r in (p0_old, p0_new, p1):
            q.offer(r, 16)
        assert q.depth() == 16
        high = self._req(8, 2)
        depth, victims = q.offer(high, 16)
        # sheds the lowest class, newest first: both p0s (8 rows
        # needed), never the p1 (4 rows would not have sufficed from
        # p0_new alone, and p1 outranks p0)
        assert victims == [p0_new, p0_old]
        assert depth == 16 and q.depth() == 16

    def test_equal_priority_never_displaces(self):
        q = RequestQueue()
        q.offer(self._req(16, 0), 16)
        with pytest.raises(ServerOverloaded,
                           match="no lower-priority rows"):
            q.offer(self._req(4, 0), 16)

    def test_insufficient_shed_rejects_arrival(self):
        q = RequestQueue()
        q.offer(self._req(2, 0), 16)    # only 2 sheddable rows: the
        q.offer(self._req(14, 10), 16)  # 14-row request OUTRANKS the
        with pytest.raises(ServerOverloaded):   # priority-9 arrival
            q.offer(self._req(8, 9), 16)
        assert q.depth() == 16          # nothing was shed on refusal

    def test_burn_shed_below_highest_queued_class(self):
        q = RequestQueue()
        q.offer(self._req(4, 1), 64)
        # budget burning + queue past the watermark: lower class sheds
        with pytest.raises(ShedForPriority, match="burning"):
            q.offer(self._req(4, 0), 64, burn_rate=2.0,
                    watermark_rows=4)
        # same class rides through regardless of burn
        depth, victims = q.offer(self._req(4, 1), 64, burn_rate=2.0,
                                 watermark_rows=4)
        assert depth == 8 and victims == []
        # healthy budget: low class admits fine past the watermark
        depth, _ = q.offer(self._req(4, 0), 64, burn_rate=0.5,
                           watermark_rows=4)
        assert depth == 12

    def test_saturation_keeps_highest_class_green(self):
        """The ISSUE's drill: under hard saturation, priority-1
        traffic stays at 100% availability while priority-0 sheds —
        lowest class first, typed."""
        server = ModelServer(ServeConfig(max_wait_s=0.0,
                                         max_queue_rows=32))
        session = server.register("m", _echo_mf(), batch_size=8)
        session._ensure_worker = lambda: None   # saturate the queue
        p0_futs = [session.submit(
            {"x": np.zeros((8, 2), np.float32)}, priority=0)
            for _ in range(4)]                  # 32 rows: FULL
        shed_before = session.metrics.shed
        p1_futs = [session.submit(
            {"x": np.full((8, 2), 7.0, np.float32)}, priority=1)
            for _ in range(2)]                  # displaces 2x p0
        shed_now = [f for f in p0_futs if f.done()]
        assert len(shed_now) == 2
        for f in shed_now:
            with pytest.raises(ServerOverloaded, match="shed"):
                f.result(timeout=1)
        assert session.metrics.shed == shed_before + 2
        assert session.metrics.shed_rows >= 16
        del session.__dict__["_ensure_worker"]  # drain what remains
        session._ensure_worker()
        for f in p1_futs:       # the highest class: 100% availability
            np.testing.assert_allclose(f.result(timeout=30)["y"], 14.0)
        for f in p0_futs:
            if f not in shed_now:
                np.testing.assert_allclose(
                    f.result(timeout=30)["y"], 0.0)
        server.close()
        assert default_registry().snapshot()["serve.shed"] >= 2

    def test_negative_priority_rejected_at_submit(self):
        server = ModelServer(ServeConfig(max_wait_s=0.0))
        server.register("m", _echo_mf(), batch_size=4)
        with pytest.raises(ValueError, match="priority"):
            server.submit({"x": np.zeros((2, 2), np.float32)},
                          priority=-1)
        server.close()

    def test_default_priority_behavior_unchanged(self):
        """With every caller at the default class there is no
        displacement and no burn shed — the pre-priority contract."""
        q = RequestQueue()
        q.offer(self._req(8, 0), 8)
        with pytest.raises(ServerOverloaded):
            q.offer(self._req(8, 0), 8, burn_rate=5.0,
                    watermark_rows=2)
