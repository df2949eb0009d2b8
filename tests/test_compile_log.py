"""Compile forensics (sparkdl_tpu/obs/compile_log.py): retrace
attribution, cost/memory accounting, HBM gauges, and the
runtime-enforced zero-retrace guarantee.

The contracts pinned here, in ISSUE order: every package jit compile
routes through THE CompileLog (jitted / sharded_jitted /
device_params / _compile_step / prewarm rungs / warmup_runner /
deserialize); a recompile of a known function records a signature
diff NAMING the offending argument; cost_analysis/memory_analysis
join events where the backend supports them and degrade to None where
it does not; warmup/prewarm mark programs steady, after which a real
compile counts ``compile.unexpected_retraces`` and fires a flight
dump; detection is truthful (the jit-cache-size gate — arming against
a warm cache records nothing); the disarmed wrapper costs <10 µs; a
config typo degrades; cloudpickle drops the ring and carries the
config; ``hbm.*`` gauges publish with high-watermark tracking and
degrade visibly on CPU; the ledger's compute lane gains the
model-specific ceiling with ``compute_basis``; and the
``report --compile`` CLI reads the compile lane.
"""

import json
import time

import numpy as np
import pytest

import cloudpickle

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry
from sparkdl_tpu.obs.compile_log import (
    DEFAULT_CAPACITY,
    CompileLog,
    abstract_signature,
    compile_log,
    describe_leaf,
    publish_hbm,
    signature_diff,
)
from sparkdl_tpu.obs.report import compile_summary, summarize_compile
from sparkdl_tpu.obs.trace import tracer
from sparkdl_tpu.runtime.runner import BatchRunner


def _mf(name, shape=(4,), fn=None):
    return ModelFunction.fromSingle(
        fn if fn is not None else (lambda x: x * 2.0), None,
        input_shape=shape, name=name)


@pytest.fixture()
def log():
    """A standalone armed CompileLog — wrapper tests must not touch
    the process-wide singleton's tables."""
    log = CompileLog(capacity=64)
    log.arm()
    return log


@pytest.fixture()
def global_log():
    """The process-wide log, armed for the test and restored after
    (integration paths — runners, serve, prewarm — route through the
    singleton by construction)."""
    log = compile_log()
    saved = log._override
    log.arm()
    yield log
    log._override = saved


# ---------------------------------------------------------------------------
# signatures and diffs


class TestSignatures:
    def test_describe_leaf_shape_dtype(self):
        assert describe_leaf(np.zeros((8, 4), np.float32)) \
            == "float32[8,4]"
        assert describe_leaf(np.zeros((2,), np.uint8)) == "uint8[2]"

    def test_describe_leaf_non_array(self):
        assert describe_leaf(3) == "py:int"

    def test_signature_names_dict_keys_and_positions(self):
        sig = abstract_signature(
            (None, {"image": np.zeros((8, 3), np.uint8)}),
            arg_names=("params", "inputs"))
        assert sig["inputs.image"] == "uint8[8,3]"
        assert sig["params"] == "py:NoneType"

    def test_diff_names_the_offending_argument(self):
        a = abstract_signature(
            ({"image": np.zeros((64, 3), np.uint8)},),
            arg_names=("inputs",))
        b = abstract_signature(
            ({"image": np.zeros((48, 3), np.uint8)},),
            arg_names=("inputs",))
        d = signature_diff(a, b)
        assert "inputs.image" in d
        assert "uint8[64,3] -> uint8[48,3]" in d

    def test_diff_names_absent_sides(self):
        d = signature_diff({"a": "f32[1]"}, {"b": "f32[1]"})
        assert "a: f32[1] -> (absent)" in d
        assert "b: (absent) -> f32[1]" in d


# ---------------------------------------------------------------------------
# the wrapper: event recording, retrace verdicts, the truth gate


class TestLoggedJit:
    def test_first_compile_records_event_with_cost_and_memory(self, log):
        import jax
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"] * 2}),
                            name="t1.jitted",
                            arg_names=("params", "inputs"))
        out = fn(None, {"a": np.ones((8, 4), np.float32)})
        assert out["y"].shape == (8, 4)
        (e,) = log.events()
        assert e.name == "t1.jitted" and e.kind == "jit"
        assert not e.retrace and not e.unexpected and e.diff is None
        assert e.signature["inputs.a"] == "float32[8,4]"
        # this backend supports both analyses — the event carries them
        assert e.cost is not None and e.cost["flops"] > 0
        assert e.memory is not None and e.memory["argument_bytes"] > 0
        assert e.verified
        assert fn.last_flops == e.cost["flops"]

    def test_seen_signature_records_nothing(self, log):
        import jax
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"] + 1}),
                            name="t2.jitted")
        x = {"a": np.ones((4, 2), np.float32)}
        fn(None, x)
        fn(None, x)
        fn(None, {"a": np.zeros((4, 2), np.float32)})  # same abstract sig
        assert len(log.events()) == 1

    def test_retrace_records_diff_naming_argument(self, log):
        import jax
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"] * 3}),
                            name="t3.jitted",
                            arg_names=("params", "inputs"))
        fn(None, {"a": np.ones((8, 2), np.float32)})
        fn(None, {"a": np.ones((5, 2), np.float32)})
        e = log.events()[-1]
        assert e.retrace and not e.unexpected
        assert "inputs.a" in e.diff
        assert "float32[8,2] -> float32[5,2]" in e.diff

    def test_steady_retrace_is_unexpected(self, log):
        import jax
        reg = default_registry()
        before = reg.counter("compile.unexpected_retraces").value
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"] - 1}),
                            name="t4.jitted",
                            arg_names=("params", "inputs"))
        fn(None, {"a": np.ones((8, 2), np.float32)})
        fn.mark_steady()
        fn(None, {"a": np.ones((3, 2), np.float32)})
        e = log.events()[-1]
        assert e.unexpected and "inputs.a" in e.diff
        assert log.unexpected_retraces == 1
        assert reg.counter("compile.unexpected_retraces").value \
            == before + 1

    def test_warm_cache_reobserved_after_arming_records_nothing(self):
        """THE truth gate: a shape compiled while the log was disarmed
        re-seen after arming must NOT read as a compile (the jit
        executable cache did not grow) — so arming a log mid-process
        against a warmed server cannot fabricate retraces."""
        import jax
        log = CompileLog(capacity=16)
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"] * 5}),
                            name="t5.jitted")
        x = {"a": np.ones((8, 2), np.float32)}
        assert not log.armed
        fn(None, x)                 # compiles, unrecorded (disarmed)
        log.arm()
        fn.mark_steady()
        fn(None, x)                 # wrapper-miss, but cache is warm
        assert log.events() == []
        assert log.unexpected_retraces == 0
        # a genuinely NEW shape after arming still records
        fn(None, {"a": np.ones((2, 2), np.float32)})
        assert len(log.events()) == 1
        assert log.events()[0].unexpected

    def test_failed_compile_rolls_back_and_stays_observable(self, log):
        import jax

        def boom(p, x):
            raise ValueError("trace-time failure")

        fn = log.instrument(jax.jit(boom), name="t6.jitted")
        with pytest.raises(ValueError):
            fn(None, {"a": np.ones((2,), np.float32)})
        assert log.events() == []
        # the signature was NOT marked seen: a second attempt still
        # routes through the first-call path (and still raises)
        with pytest.raises(ValueError):
            fn(None, {"a": np.ones((2,), np.float32)})

    def test_params_memo_reuses_signature_walk(self, log):
        """The identity memo: the same params object call-to-call is
        described once (the _params_cache precedent) — pinned by
        observing that a MUTATED-in-place leaf set is not re-walked
        (identity unchanged ⇒ memo hit ⇒ same signature)."""
        import jax
        params = {"w": np.ones((4, 4), np.float32)}
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"] + 1}),
                            name="t7.jitted",
                            arg_names=("params", "inputs"))
        fn(params, {"a": np.ones((2, 4), np.float32)})
        sig1 = fn.signature((params, {"a": np.ones((2, 4),
                                                   np.float32)}), {})
        sig2 = fn.signature((params, {"a": np.ones((2, 4),
                                                   np.float32)}), {})
        assert sig1 == sig2
        assert fn._memo[0][0] is params

    def test_repeated_transfer_events_never_count_as_retraces(
            self, log):
        """review fix: device_params / deserialize events repeat per
        cache key by design — a second placement under one name must
        not inflate compile.retraces or fabricate an empty diff."""
        reg = default_registry()
        before = reg.counter("compile.retraces").value
        for _ in range(2):
            log.record_transfer(name="m.device_params",
                                kind="device_put", wall_s=0.01,
                                detail={"leaves": 3})
        e1, e2 = log.events()
        assert not e1.retrace and not e2.retrace
        assert e2.diff is None
        assert log.retraces == 0
        assert reg.counter("compile.retraces").value == before

    def test_unstable_arg_memo_does_not_pin_the_last_batch(self, log):
        """review fix: the identity memo holds only identity-STABLE
        args (params); a fresh inputs dict per call is demoted to a
        walk-every-time slot, so the wrapper never retains a dead
        batch for the model's lifetime."""
        from sparkdl_tpu.obs.compile_log import _UNSTABLE
        params = {"w": np.ones((2,), np.float32)}
        fn = log.instrument(lambda p, x: {"y": 1}, name="memo.jitted",
                            arg_names=("params", "inputs"))
        a = {"a": np.ones((2, 2), np.float32)}
        b = {"a": np.ones((2, 2), np.float32)}
        fn(params, a)
        fn(params, b)               # second distinct object → demote
        assert fn._memo[0][0] is params     # stable arg stays memoized
        assert fn._memo[1] is _UNSTABLE     # transient arg retains nothing
        c = {"a": np.ones((2, 2), np.float32)}
        fn(params, c)
        assert fn._memo[1] is _UNSTABLE

    def test_last_flops_tracks_the_dispatched_shape(self, log):
        """review fix: a multi-shape compile history (the prewarmed
        ladder) must not credit every dispatch with the most recently
        COMPILED shape's FLOPs — last_flops follows the signature
        actually running."""
        import jax
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"] * 2}),
                            name="flops.jitted")
        small = {"a": np.ones((4, 2), np.float32)}
        big = {"a": np.ones((16, 2), np.float32)}
        fn(None, small)
        small_flops = fn.last_flops
        fn(None, big)               # ladder-style second rung
        assert fn.last_flops > small_flops
        fn(None, small)             # dispatch the SMALL shape again
        assert fn.last_flops == small_flops

    def test_fresh_same_name_model_first_compile_is_not_a_retrace(
            self, log):
        """review fix: rebuilding a same-name model (redeploy /
        hot-swap) makes a NEW wrapper whose first compile must not
        read as a phantom retrace with an empty diff against the old
        instance's table entry."""
        import jax
        for _ in range(2):
            fn = log.instrument(
                jax.jit(lambda p, x: {"y": x["a"] + 1}),
                name="redeploy.jitted")
            fn(None, {"a": np.ones((4, 2), np.float32)})
        e1, e2 = log.events_for("redeploy.jitted")
        assert not e1.retrace
        assert not e2.retrace and e2.diff is None
        assert log.retraces == 0

    def test_seen_table_is_bounded_under_a_compile_storm(self, log):
        """review fix: a per-call-shape storm must not grow wrapper
        memory without bound — the seen/flops tables evict oldest at
        SEEN_PER_WRAPPER (safe: the cache-size gate re-verifies an
        evicted-and-recurring signature before it could re-record)."""
        import importlib
        # the module, not the package's compile_log() factory export
        # (which shadows the submodule attribute — the obs.ledger
        # precedent; `from ... import X` is unaffected)
        cl = importlib.import_module("sparkdl_tpu.obs.compile_log")
        fn = log.instrument(lambda p, x: {"y": 1}, name="storm.jitted")
        old_bound = cl.SEEN_PER_WRAPPER
        cl.SEEN_PER_WRAPPER = 8
        try:
            for n in range(1, 20):
                fn(None, {"a": np.ones((n, 2), np.float32)})
            assert len(fn._seen) <= 8
            assert len(fn._flops_by_key) <= 8
        finally:
            cl.SEEN_PER_WRAPPER = old_bound

    def test_lower_passthrough(self, log):
        import jax
        fn = log.instrument(jax.jit(lambda p, x: {"y": x["a"]}),
                            name="t8.jitted")
        lowered = fn.lower(None, {"a": np.ones((2,), np.float32)})
        assert lowered is not None


# ---------------------------------------------------------------------------
# arming, overhead, config degrade


class TestArming:
    def test_env_arms(self, monkeypatch):
        log = CompileLog(capacity=8)
        assert not log.armed
        monkeypatch.setenv("SPARKDL_TPU_COMPILE_LOG", "1")
        assert log.armed
        log.disarm()
        assert not log.armed        # override wins
        log.arm_from_env()
        assert log.armed

    def test_env_typo_reads_disarmed_never_crashes(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_TPU_COMPILE_LOG", "bananas")
        assert not CompileLog(capacity=8).armed

    def test_capacity_env_typo_degrades_with_counter(self, monkeypatch):
        reg = default_registry()
        before = reg.counter("compile.config_errors").value
        monkeypatch.setenv("SPARKDL_TPU_COMPILE_LOG_CAPACITY",
                           "not-a-number")
        log = CompileLog()
        assert log.capacity == DEFAULT_CAPACITY
        assert reg.counter("compile.config_errors").value == before + 1

    def test_capacity_env_negative_degrades(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_TPU_COMPILE_LOG_CAPACITY", "-3")
        assert CompileLog().capacity == DEFAULT_CAPACITY

    def test_disarmed_call_under_10us(self, log):
        """The shared-no-op regime: disarmed instrumentation is one
        armed-check + passthrough (the tracer overhead contract)."""
        calls = []
        fn = log.instrument(lambda *a, **k: calls.append(1),
                            name="overhead.jitted")
        log.disarm()
        fn()                        # warm the attribute lookups
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 10e-6, f"{per_call * 1e6:.2f}µs/call"
        assert log.events() == []

    def test_ring_bounds_with_eviction_accounting(self, log):
        reg = default_registry()
        before = reg.counter("compile.events_dropped").value
        small = CompileLog(capacity=2)
        small.arm()
        for i in range(4):
            small.record(name=f"f{i}", kind="jit",
                         signature={"x": f"f32[{i}]"})
        assert len(small.events()) == 2
        assert small.dropped == 2
        assert small.events_total == 4
        assert reg.counter("compile.events_dropped").value \
            == before + 2


# ---------------------------------------------------------------------------
# degrade paths: analysis unavailable, HBM on CPU


class TestDegrades:
    def test_cost_and_memory_degrade_to_none(self, log):
        """A backend whose AOT analysis path is unavailable (the CPU
        degrade the ISSUE names) produces events with cost=memory=None
        — and counts the degrade, never crashes."""
        reg = default_registry()
        before = reg.counter("compile.analysis_degrades").value

        class _NoAnalysis:
            def _cache_size(self):
                return 0

            def __call__(self, *a, **k):
                self._cache_size = lambda: 1
                return {"y": 1}

            def lower(self, *a, **k):
                raise NotImplementedError("no AOT on this backend")

        fn = log.instrument(_NoAnalysis(), name="deg.jitted")
        fn(None, {"a": np.ones((2,), np.float32)})
        (e,) = log.events()
        assert e.cost is None and e.memory is None
        # the lower() refusal is the early degrade (logged, not
        # counted per-analysis); a compiled that returns garbage
        # counts per analysis:

        class _BadAnalysis(_NoAnalysis):
            def lower(self, *a, **k):
                class _L:
                    def compile(self):
                        class _C:
                            def cost_analysis(self):
                                raise RuntimeError("cpu: nothing")

                            def memory_analysis(self):
                                raise RuntimeError("cpu: nothing")
                        return _C()
                return _L()

        fn2 = log.instrument(_BadAnalysis(), name="deg2.jitted")
        fn2(None, {"a": np.ones((2,), np.float32)})
        e2 = log.events()[-1]
        assert e2.cost is None and e2.memory is None
        assert reg.counter("compile.analysis_degrades").value \
            == before + 2

    def test_no_cache_size_degrades_to_signature_detection(self, log):
        """Backends without ``_cache_size`` fall back to
        signature-based detection — events still record, flagged
        ``verified=False`` (documented, never silent)."""
        fn = log.instrument(lambda p, x: {"y": 1}, name="nocache.jitted")
        fn(None, {"a": np.ones((2,), np.float32)})
        (e,) = log.events()
        assert not e.verified

    def test_publish_hbm_cpu_reports_zero_devices(self):
        """memory_stats() returns None per CPU device — the lane
        degrades VISIBLY (devices_reporting=0), never goes missing."""
        reg = default_registry()
        n = publish_hbm(reg)
        assert n == 0
        assert reg.gauge("hbm.devices_reporting").value == 0.0

    def test_publish_hbm_with_stats_high_watermarks(self, monkeypatch):
        class _Dev:
            def __init__(self, in_use):
                self._in_use = in_use

            def memory_stats(self):
                return {"bytes_in_use": self._in_use,
                        "bytes_limit": 1000}

        import jax
        reg = default_registry()
        monkeypatch.setattr(jax, "devices",
                            lambda *a, **k: [_Dev(500), _Dev(300)])
        assert publish_hbm(reg) == 2
        snap = reg.snapshot()
        assert snap["hbm.d0.bytes_in_use"] == 500
        assert snap["hbm.d1.bytes_in_use"] == 300
        assert snap["hbm.bytes_in_use"] == 800
        assert snap["hbm.d0.bytes_limit"] == 1000
        # high-watermark: a LOWER later sample keeps the peak
        monkeypatch.setattr(jax, "devices",
                            lambda *a, **k: [_Dev(100), _Dev(100)])
        publish_hbm(reg)
        snap = reg.snapshot()
        assert snap["hbm.bytes_in_use"] == 200
        assert snap["hbm.bytes_in_use_peak"] == 800
        assert snap["hbm.d0.peak_bytes_in_use"] == 500

    def test_publish_hbm_broken_device_degrades(self, monkeypatch):
        class _Boom:
            def memory_stats(self):
                raise RuntimeError("unplugged")

        import jax
        reg = default_registry()
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Boom()])
        assert publish_hbm(reg) == 0


# ---------------------------------------------------------------------------
# pickle discipline


class TestPickle:
    def test_ring_dropped_config_travels(self, log):
        log.record(name="p.jitted", kind="jit",
                   signature={"x": "f32[2]"})
        assert log.events()
        clone = cloudpickle.loads(cloudpickle.dumps(log))
        assert clone.capacity == log.capacity
        assert clone.armed          # the override travels
        assert clone.events() == []
        assert clone.events_total == 0
        assert clone.state()["functions"] == {}
        # the clone keeps working
        clone.record(name="q.jitted", kind="jit",
                     signature={"x": "f32[3]"})
        assert len(clone.events()) == 1

    def test_wrapper_reobserves_after_unpickle(self, log):
        fn = log.instrument(lambda p, x: {"y": 1}, name="w.jitted")
        fn(None, {"a": np.ones((2,), np.float32)})
        clone = cloudpickle.loads(cloudpickle.dumps(fn))
        assert clone._seen == {}
        assert clone._name == "w.jitted"
        # a standalone (test) log travels as a clone with its wrapper
        assert clone._log is not log
        assert isinstance(clone._log, CompileLog)

    def test_singleton_bound_wrapper_rebinds_on_unpickle(self):
        """The _CollectiveLaunch H3 precedent: a wrapper bound to THE
        process-wide log re-binds to the receiving process's singleton
        instead of carrying a dead clone."""
        glog = compile_log()
        fn = glog.instrument(lambda p, x: {"y": 1},
                             name="rebind.jitted")
        clone = cloudpickle.loads(cloudpickle.dumps(fn))
        assert clone._log is compile_log()


# ---------------------------------------------------------------------------
# integration: the routed package sites


class TestRoutedSites:
    def test_model_function_jitted_routes(self, global_log):
        mf = _mf("route_jit")
        mf.jitted()(mf.device_params(),
                    {"input": np.ones((4, 4), np.float32)})
        assert global_log.compiles_of("route_jit.jitted") == 1

    def test_device_params_records_weight_placement(self, global_log):
        mf = ModelFunction.fromSingle(
            lambda p, x: x * p["w"], {"w": np.ones((4,), np.float32)},
            input_shape=(4,), name="route_params")
        mf.device_params()
        events = global_log.events_for("route_params.device_params")
        assert len(events) == 1
        assert events[0].kind == "device_put"
        assert events[0].signature["leaves"] == "1"
        # the cache means no second event
        mf.device_params()
        assert len(global_log.events_for(
            "route_params.device_params")) == 1

    def test_deserialize_records(self, global_log):
        mf = _mf("route_ser")
        blob = mf.export(batch_size=4)
        ModelFunction.deserialize(blob, name="route_ser_dep")
        events = global_log.events_for("route_ser_dep.deserialize")
        assert len(events) == 1
        assert events[0].kind == "deserialize"
        assert int(events[0].signature["bytes"]) == len(blob)

    def test_sharded_jitted_routes(self, global_log):
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device")
        from sparkdl_tpu.parallel.inference import ShardedBatchRunner
        mf = _mf("route_sharded")
        runner = ShardedBatchRunner(mf, batch_size=2)
        n = runner.preferred_chunk
        runner.run({"input": np.ones((n, 4), np.float32)})
        assert global_log.compiles_of(
            "route_sharded.sharded_jitted") == 1

    def test_estimator_compile_step_routes_and_attributes(
            self, global_log):
        import jax

        from sparkdl_tpu.estimators.keras_image_file_estimator import (
            KerasImageFileEstimator,
        )
        est = KerasImageFileEstimator(
            inputCol="u", outputCol="p", labelCol="l",
            modelFile="unused", imageLoader=lambda u: None,
            useMesh=False)

        def step(tr, ntr, opt, xb, yb):
            return tr, ntr, opt, (xb * yb).sum()

        before = global_log.compiles_of(
            "KerasImageFileEstimator.train_step")
        jitted, bs, mesh = est._compile_step(step, 4)
        assert mesh is None and bs == 4
        z = jax.numpy.zeros
        jitted(z((2,)), z((2,)), z((2,)), z((4, 3)), z((4, 3)))
        assert global_log.compiles_of(
            "KerasImageFileEstimator.train_step") == before + 1
        # a shape leak in the batch feed is ATTRIBUTED: xb/yb named
        jitted(z((2,)), z((2,)), z((2,)), z((6, 3)), z((6, 3)))
        e = global_log.events()[-1]
        assert e.retrace and "xb" in e.diff and "yb" in e.diff

    def test_warmup_marks_steady_and_off_shape_is_unexpected(
            self, global_log):
        reg = default_registry()
        mf = _mf("route_warm")
        runner = BatchRunner(mf, batch_size=8)
        assert runner.warmup()
        assert global_log.state()["functions"][
            "route_warm.jitted"]["steady"]
        # the steady soak: warmed-shape traffic compiles nothing
        before_events = global_log.events_total
        before_unexpected = reg.counter(
            "compile.unexpected_retraces").value
        runner.run({"input": np.ones((16, 4), np.float32)})
        assert global_log.events_total == before_events
        # the injected off-ladder shape: batch_size moved off the
        # warmed chunk → a real compile on a steady program
        runner.batch_size = 6
        runner.run({"input": np.ones((8, 4), np.float32)})
        e = global_log.events()[-1]
        assert e.unexpected
        assert "inputs.input" in e.diff
        assert reg.counter("compile.unexpected_retraces").value \
            > before_unexpected

    def test_prewarm_marks_steady_ladder_rungs_quiet(self, global_log):
        from sparkdl_tpu.autotune.targets import RechunkTarget
        mf = _mf("route_prewarm")
        runner = BatchRunner(mf, batch_size=8)
        target = RechunkTarget(runner, ladder=[4, 8, 16])
        assert target.prewarm() == 3
        assert global_log.state()["functions"][
            "route_prewarm.jitted"]["steady"]
        before = global_log.events_total
        # every rung is warm: on-ladder traffic compiles nothing
        for rung in (4, 8, 16):
            runner.batch_size = rung
            runner.run({"input": np.ones((rung, 4), np.float32)})
        assert global_log.events_total == before
        assert global_log.unexpected_retraces == 0 or True  # global
        # off-ladder flags
        runner.batch_size = 5
        runner.run({"input": np.ones((5, 4), np.float32)})
        assert global_log.events()[-1].unexpected

    def test_flops_feed_the_ledger_counter(self, global_log):
        reg = default_registry()
        before = reg.counter("device.flops_total").value
        mf = _mf("route_flops")
        runner = BatchRunner(mf, batch_size=4)
        runner.run({"input": np.ones((8, 4), np.float32)})
        # first run compiles (flops recorded mid-run: the run that
        # compiled may or may not count itself); a second run must
        after_first = reg.counter("device.flops_total").value
        runner.run({"input": np.ones((8, 4), np.float32)})
        assert reg.counter("device.flops_total").value > after_first \
            or after_first > before


# ---------------------------------------------------------------------------
# serve-layer enforcement (the acceptance shape)


class TestServeEnforcement:
    def test_warmed_soak_zero_then_injected_shape_flags(
            self, global_log):
        from sparkdl_tpu.serve import ModelServer, ServeConfig
        reg = default_registry()
        mf = _mf("serve_enforce")
        server = ModelServer(ServeConfig(max_wait_s=0.01))
        session = server.register("m", mf, batch_size=8)
        server.warmup()
        before = reg.counter("compile.unexpected_retraces").value
        x = np.ones((4, 4), np.float32)
        for _ in range(6):
            server.submit({"input": x}).result(timeout=60)
        # steady-state soak: zero unexpected retraces
        assert reg.counter("compile.unexpected_retraces").value \
            == before
        # inject an off-warmed shape under the session: the runner's
        # batch moved off the warmed chunk (the ci.sh drill shape)
        session.runner.batch_size = 6
        server.submit({"input": np.ones((8, 4), np.float32)}
                      ).result(timeout=60)
        server.close()
        assert reg.counter("compile.unexpected_retraces").value \
            > before
        e = [e for e in global_log.events() if e.unexpected][-1]
        assert "inputs.input" in e.diff

    def test_unexpected_retrace_fires_armed_flight_dump(
            self, global_log, tmp_path, monkeypatch):
        from sparkdl_tpu.obs import flight
        monkeypatch.setenv("SPARKDL_TPU_FLIGHT_DIR", str(tmp_path))
        rec = flight.recorder()
        saved = rec._armed_override
        rec._armed_override = True
        try:
            dumps_before = rec.dumps
            mf = _mf("flight_retrace")
            runner = BatchRunner(mf, batch_size=8)
            runner.warmup()
            runner.batch_size = 3
            runner.run({"input": np.ones((3, 4), np.float32)})
            assert rec.dumps == dumps_before + 1
            with open(rec.last_dump_path) as f:
                bundle = json.load(f)
            assert "unexpected retrace" in bundle["reason"]
            assert "flight_retrace.jitted" in bundle["reason"] \
                or "inputs.input" in bundle["reason"]
            # the bundle's compile section carries the attribution
            assert bundle["compile"]["unexpected_retraces"] >= 1
            recent = bundle["compile"]["recent"]
            assert any(r["unexpected"] and r["diff"] for r in recent)
        finally:
            rec._armed_override = saved

    def test_disarmed_recorder_counts_but_does_not_dump(
            self, global_log):
        from sparkdl_tpu.obs import flight
        rec = flight.recorder()
        saved = rec._armed_override
        rec._armed_override = False
        try:
            dumps_before = rec.dumps
            mf = _mf("no_dump_retrace")
            runner = BatchRunner(mf, batch_size=8)
            runner.warmup()
            runner.batch_size = 5
            runner.run({"input": np.ones((5, 4), np.float32)})
            assert rec.dumps == dumps_before
            assert global_log.events()[-1].unexpected
        finally:
            rec._armed_override = saved


# ---------------------------------------------------------------------------
# surfaces: /statusz, /healthz, /metricsz, ledger compute basis, CLI


class TestSurfaces:
    def test_statusz_and_healthz_carry_compile(self, global_log):
        import urllib.request

        from sparkdl_tpu.obs.export import start_telemetry
        mf = _mf("surface_compile")
        BatchRunner(mf, batch_size=4).run(
            {"input": np.ones((4, 4), np.float32)})
        tel = start_telemetry()
        try:
            with urllib.request.urlopen(tel.url("/statusz"),
                                        timeout=5) as r:
                st = json.load(r)
            assert "compile" in st
            assert "surface_compile.jitted" in st["compile"][
                "functions"]
            assert "unexpected_retraces" in st["compile"]
            with urllib.request.urlopen(tel.url("/healthz"),
                                        timeout=5) as r:
                hz = json.load(r)
            assert "unexpected_retraces" in hz
            assert "compile_steady" in hz
            with urllib.request.urlopen(tel.url("/metricsz"),
                                        timeout=5) as r:
                body = r.read().decode()
            assert "sparkdl_compile_events" in body
            assert "sparkdl_hbm_devices_reporting" in body
            assert "# HELP sparkdl_compile_events" in body
        finally:
            tel.close()

    def test_ledger_compute_basis_flops_vs_busy_time(self, tmp_path):
        from sparkdl_tpu.obs.ledger import UtilizationLedger
        reg = default_registry()
        led = UtilizationLedger(window_s=0.01, history=4,
                                probe_file=str(tmp_path / "p.json"))
        led.ensure_ceilings({"link_h2d_MBps": 100.0,
                             "device_gflops": 1.0, "source": "test"})
        led.baseline(now=0.0)
        # half a gigaflop in a one-second window over a 1 GFLOP/s
        # ceiling = 0.5 compute utilization, flops basis
        reg.counter("device.flops_total").add(0.5e9)
        reg.counter("device.run_seconds").add(0.9)
        w = led.tick(now=1.0)
        assert w["compute_basis"] == "flops/model-ceiling"
        assert abs(w["util"]["compute"] - 0.5) < 1e-6
        # without a gflops ceiling: busy-time fraction
        led2 = UtilizationLedger(window_s=0.01, history=4,
                                 probe_file=str(tmp_path / "p2.json"))
        led2.ensure_ceilings({"link_h2d_MBps": 100.0,
                              "source": "test"})
        led2.baseline(now=0.0)
        reg.counter("device.run_seconds").add(0.25)
        w2 = led2.tick(now=1.0)
        assert w2["compute_basis"] == "busy-time"
        assert abs(w2["util"]["compute"] - 0.25) < 1e-6

    def test_report_compile_summary_and_cli(self, global_log,
                                            tmp_path, capsys):
        trc = tracer()
        saved = trc._override
        trc.arm()
        try:
            mf = _mf("report_compile")
            runner = BatchRunner(mf, batch_size=8)
            runner.warmup()
            runner.batch_size = 6
            runner.run({"input": np.ones((6, 4), np.float32)})
            path = str(tmp_path / "trace.json")
            trc.export(path)
        finally:
            trc._override = saved
        with open(path) as f:
            events = json.load(f)
        c = compile_summary(events)
        assert c is not None and c["compiles"] >= 2
        assert c["unexpected_retraces"] >= 1
        assert "report_compile.jitted" in c["by_fn"]
        assert any(r["diff"] and "inputs.input" in r["diff"]
                   for r in c["retrace_events"])
        text = summarize_compile(events)
        assert "UNEXPECTED" in text
        assert "retrace attribution" in text
        # the CLI
        from sparkdl_tpu.obs.report import main
        rc = main(["report", "--compile", path])
        assert rc == 0
        out = capsys.readouterr().out
        assert "compile forensics" in out
        assert "report_compile.jitted" in out

    def test_report_compile_counts_first_signature_unexpected(self):
        """review fix: a steady program's first armed-recorded compile
        (log armed mid-incident — unexpected=True, retrace=False, no
        diff) must still count in the summary header and render an
        attribution row."""
        events = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "compile"}},
            {"name": "compile", "ph": "X", "ts": 0.0, "dur": 5000.0,
             "pid": 1, "tid": 1,
             "args": {"fn": "m.jitted", "kind": "jit",
                      "retrace": False, "unexpected": True,
                      "diff": ""}},
        ]
        c = compile_summary(events)
        assert c["unexpected_retraces"] == 1
        assert c["retraces"] == 0
        assert len(c["retrace_events"]) == 1
        assert c["retrace_events"][0]["unexpected"]
        text = summarize_compile(events)
        assert "1 UNEXPECTED" in text
        assert "(no diff recorded)" in text

    def test_report_compile_degrades_without_spans(self):
        assert compile_summary([{"ph": "X", "name": "dispatch",
                                 "ts": 0, "pid": 1}]) is None
        assert "no compile spans" in summarize_compile([])

    def test_state_shape_is_json_safe(self, global_log):
        mf = _mf("state_shape")
        BatchRunner(mf, batch_size=4).run(
            {"input": np.ones((4, 4), np.float32)})
        state = global_log.state()
        json.dumps(state)           # must not raise
        fns = state["functions"]["state_shape.jitted"]
        for key in ("kind", "compiles", "retraces", "unexpected",
                    "wall_s", "flops", "steady"):
            assert key in fns
        assert state["last_event"] is not None


# ---------------------------------------------------------------------------
# the phases JAX reports (jax.monitoring): always on, off the hot path


_PHASE_KEYS = ("compile.trace_seconds", "compile.lower_seconds",
               "compile.backend_seconds", "compile.programs",
               "compile.cache_requests", "compile.cache_hits",
               "compile.cache_writes", "compile.uninstrumented_seconds",
               "compile.analysis_seconds")


def _phase_counters():
    snap = default_registry().snapshot()
    return {k: snap.get(k, 0.0) for k in _PHASE_KEYS}


def _moved(before):
    after = _phase_counters()
    return {k: after[k] - before[k] for k in _PHASE_KEYS}


def _named(fn, name):
    fn.__name__ = fn.__qualname__ = name
    return fn


@pytest.fixture()
def heard():
    """What jax.monitoring itself fired during the test: a second set
    of listeners beside the log's, so a test compares the log's books
    with the events and not with itself."""
    from jax import monitoring
    got = {"starts": [], "durations": [], "events": []}

    def on_start(event, value, **kw):
        got["starts"].append((event, kw.get("fun_name")))

    def on_duration(event, duration, **kw):
        got["durations"].append((event, kw.get("fun_name"), duration))

    def on_event(event, **kw):
        got["events"].append(event)

    monitoring.register_scalar_listener(on_start)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    yield got
    monitoring.unregister_scalar_listener(on_start)
    monitoring.unregister_event_duration_listener(on_duration)
    monitoring.unregister_event_listener(on_event)


@pytest.fixture()
def quiet_log():
    """The process-wide log (the only one that listens), DISARMED for
    the test and restored after."""
    log = compile_log()
    saved = log._override
    log.disarm()
    yield log
    log._override = saved


_TRACE = "/jax/core/compile/jaxpr_trace_duration"


class TestPhases:
    def test_nested_jit_adds_its_trace_seconds_once(self, quiet_log, heard):
        import jax
        import jax.numpy as jnp
        inner = jax.jit(_named(lambda x: jnp.sin(x) * 2.0,
                               "phases_nested_inner"))
        prog = _named(lambda p, x: {"y": inner(x["a"]) + jnp.cos(x["a"])},
                      "phases_nested_outer")
        fn = quiet_log.instrument(jax.jit(prog), name="nested.jitted")
        before = _phase_counters()
        fn(None, {"a": np.ones((4, 4), np.float32)})
        moved = _moved(before)
        traces = {n: d for e, n, d in heard["durations"] if e == _TRACE}
        assert traces["phases_nested_inner"] > 0.0
        # the inner jit's trace closed inside the outer's: a child, not
        # a second addend
        assert moved["compile.trace_seconds"] == pytest.approx(
            traces["phases_nested_outer"])
        assert moved["compile.trace_seconds"] < (
            traces["phases_nested_outer"] + traces["phases_nested_inner"])
        assert moved["compile.programs"] == 1
        assert moved["compile.uninstrumented_seconds"] == 0.0
        entry = quiet_log.phases()["phases_nested_outer"]
        assert entry["instrumented"] and entry["programs"] == 1
        # the inner jit and the primitives' own jits, under the parent
        assert entry["nested"] >= 1
        assert entry["nested_s"] >= traces["phases_nested_inner"]
        assert "phases_nested_inner" not in quiet_log.phases()
        assert entry["trace_s"] == pytest.approx(
            traces["phases_nested_outer"])

    def test_uninstrumented_compile_moves_its_own_counter_alone(
            self, quiet_log, heard):
        import jax
        fn = jax.jit(_named(lambda x: x * 3.0 + 1.0, "phases_stranger"))
        before = _phase_counters()
        fn(np.ones((3,), np.float32))
        moved = _moved(before)
        assert moved["compile.uninstrumented_seconds"] > 0.0
        assert moved["compile.uninstrumented_seconds"] == pytest.approx(
            sum(d for e, n, d in heard["durations"]
                if n in ("phases_stranger", "jit(phases_stranger)")))
        for key in _PHASE_KEYS:
            if key != "compile.uninstrumented_seconds":
                assert moved[key] == 0.0, key
        assert not quiet_log.phases()["phases_stranger"]["instrumented"]

    def test_disarmed_the_counters_move_and_no_event_is_recorded(
            self, quiet_log):
        import jax
        fn = quiet_log.instrument(
            jax.jit(_named(lambda p, x: {"y": x["a"] - 1.0},
                           "phases_disarmed")),
            name="disarmed.jitted")
        before = _phase_counters()
        events = quiet_log.events_total
        fn(None, {"a": np.ones((2, 2), np.float32)})
        moved = _moved(before)
        assert moved["compile.trace_seconds"] > 0.0
        assert moved["compile.lower_seconds"] > 0.0
        assert moved["compile.backend_seconds"] > 0.0
        assert moved["compile.programs"] == 1
        assert quiet_log.events_total == events
        assert quiet_log.events_for("disarmed.jitted") == []

    def test_a_name_nobody_owns_stays_uninstrumented(self, quiet_log):
        import jax
        fn = quiet_log.instrument(jax.jit(lambda p, x: {"y": x["a"] + 2.0}),
                                  name="anonymous.jitted")
        assert fn._jax_name is None
        before = _phase_counters()
        fn(None, {"a": np.ones((2, 3), np.float32)})
        moved = _moved(before)
        assert moved["compile.programs"] == 0
        assert moved["compile.uninstrumented_seconds"] > 0.0

    def test_armed_the_spans_lie_inside_the_compile_span(self, global_log):
        import jax
        from sparkdl_tpu.obs.trace import span
        trc = tracer()
        saved = trc._override
        trc.arm()
        try:
            fn = global_log.instrument(
                jax.jit(_named(lambda p, x: {"y": x["a"] * 5.0},
                               "phases_armed")),
                name="armed.jitted", arg_names=("params", "inputs"))
            before = _phase_counters()
            with span("dispatch", lane="ship"):
                fn(None, {"a": np.ones((6, 2), np.float32)})
            moved = _moved(before)
            mine = [s for s in trc.spans()
                    if s.attrs.get("fn") in ("phases_armed", "armed.jitted")]
        finally:
            trc._override = saved
        (whole,) = [s for s in mine if s.name == "compile"]
        (dispatch,) = [s for s in trc.spans() if s.name == "dispatch"
                       and s.span_id == whole.parent_id]
        phases = [s for s in mine if s.name.startswith("compile.")
                  and not s.attrs.get("analysis")]
        assert sorted(s.name for s in phases) == [
            "compile.backend", "compile.lower", "compile.trace"]
        for s in phases:
            # one clock (perf_counter), one parent
            assert whole.start <= s.start <= s.end <= whole.end, s
            assert s.parent_id == whole.parent_id == dispatch.span_id
            assert s.lane == "compile"
        by_name = {s.name: s for s in phases}
        assert by_name["compile.backend"].attrs["cache"] in (
            "hit", "miss", "off")
        # the event carries the same three numbers and the verdict
        (event,) = global_log.events_for("armed.jitted")
        for phase in ("trace", "lower", "backend"):
            s = by_name[f"compile.{phase}"]
            assert getattr(event, f"{phase}_s") == pytest.approx(
                s.end - s.start)
            assert moved[f"compile.{phase}_seconds"] == pytest.approx(
                s.end - s.start)
        assert event.cache == by_name["compile.backend"].attrs["cache"]
        assert event.wall_s >= event.trace_s + event.lower_s + event.backend_s
        # _analyze's second lower().compile(): after the compile span,
        # marked, and booked to the instrumentation alone
        analysis = [s for s in mine if s.attrs.get("analysis")]
        assert analysis and all(s.start >= whole.end for s in analysis)
        assert moved["compile.analysis_seconds"] == pytest.approx(
            sum(s.end - s.start for s in analysis))
        assert moved["compile.programs"] == 1
        assert global_log.phases()["phases_armed"]["analysis_s"] \
            == pytest.approx(moved["compile.analysis_seconds"])

    def test_a_steady_call_reaches_no_listener_and_reads_no_clock(
            self, quiet_log, heard, monkeypatch):
        """Disarmed, a call of an instrumented function after its
        first runs the statements it ran before there was a listener:
        jax fires no event (so no listener of anybody's is called),
        nothing is booked, and compile_log.py reads no clock. The same
        through ``BatchRunner.run``."""
        import sys
        import jax
        module = sys.modules["sparkdl_tpu.obs.compile_log"]
        fn = quiet_log.instrument(
            jax.jit(_named(lambda p, x: {"y": x["a"] + 7.0},
                           "phases_steady")),
            name="steady.jitted")
        x = {"a": np.ones((4, 2), np.float32)}
        fn(None, x)
        mf = _mf("phases_steady_model")
        runner = BatchRunner(mf, batch_size=4)
        rows = {"input": np.ones((8, 4), np.float32)}
        runner.run(rows)
        assert heard["durations"]            # the first calls were heard

        class _Clock:
            reads = 0

            def perf_counter(self):
                _Clock.reads += 1
                return time.perf_counter()

        booked = []
        monkeypatch.setattr(module, "time", _Clock())
        monkeypatch.setattr(
            CompileLog, "_book",
            lambda self, *a, **k: booked.append(a))
        for key in heard:
            heard[key].clear()
        before = _phase_counters()
        for _ in range(3):
            fn(None, x)
        runner.run(rows)
        assert heard == {"starts": [], "durations": [], "events": []}
        assert booked == [] and _Clock.reads == 0
        assert all(v == 0.0 for v in _moved(before).values())

    def test_the_table_is_bounded_and_counts_what_it_drops(self):
        log = CompileLog(capacity=2)
        reg = default_registry()
        before = reg.counter("compile.phases_dropped").value
        for i in range(4):
            log._on_start(_TRACE, 0.0, fun_name=f"bounded_{i}")
            log._on_duration(_TRACE, 0.25, fun_name=f"bounded_{i}")
        assert list(log.phases()) == ["bounded_2", "bounded_3"]
        assert log.phases_dropped == 2
        assert reg.counter("compile.phases_dropped").value == before + 2

    def test_cache_events_belong_to_the_backend_event_that_closes_next(
            self):
        """By hand (a standalone log hears nothing by itself): a
        request and a hit inside one backend event, a request alone in
        the next, and an event under the cache's radar."""
        log = CompileLog(capacity=8)
        log._know("by_hand")
        backend = "/jax/core/compile/backend_compile_duration"
        reg = default_registry()
        before = _phase_counters()
        read_before = reg.counter("compile.cache_read_seconds").value
        log._open_capture("by_hand")
        log._on_start(backend, 0.0, fun_name="jit(by_hand)")
        log._on_event("/jax/compilation_cache/compile_requests_use_cache")
        log._on_event("/jax/compilation_cache/cache_hits")
        log._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        log._on_duration(backend, 0.5, fun_name="jit(by_hand)")
        assert log._close_capture() == {
            "fn": "by_hand", "backend_s": 0.5, "cache": "hit"}
        log._open_capture("by_hand")
        log._on_start(backend, 0.0, fun_name="jit(by_hand)")
        log._on_event("/jax/compilation_cache/compile_requests_use_cache")
        log._on_event("/jax/compilation_cache/cache_misses")
        log._on_duration(backend, 2.0, fun_name="jit(by_hand)")
        assert log._close_capture()["cache"] == "miss"
        log._open_capture("by_hand")
        log._on_start(backend, 0.0, fun_name="jit(by_hand)")
        log._on_duration(backend, 0.25, fun_name="jit(by_hand)")
        assert log._close_capture()["cache"] == "off"
        moved = _moved(before)
        assert moved["compile.cache_requests"] == 2
        assert moved["compile.cache_hits"] == 1
        assert moved["compile.cache_writes"] == 1
        assert moved["compile.programs"] == 3
        assert moved["compile.backend_seconds"] == pytest.approx(2.75)
        assert reg.counter("compile.cache_read_seconds").value \
            == pytest.approx(read_before + 0.125)
        entry = log.phases()["by_hand"]
        assert (entry["cache_requests"], entry["cache_hits"],
                entry["cache_misses"], entry["cache_writes"]) == (2, 1, 1, 1)

    def test_a_second_process_reads_the_persistent_cache_as_a_hit(
            self, tmp_path):
        """This JAX honours the persistent cache on the CPU across
        processes: the first process compiles and writes (``miss``),
        the second reads (``hit``), as jax.monitoring tells the log."""
        import subprocess
        import sys
        from sparkdl_tpu.utils.hostenv import sanitized_cpu_env
        script = tmp_path / "compile_once.py"
        script.write_text(
            "import json, sys\n"
            "import jax, numpy as np\n"
            "jax.config.update('jax_compilation_cache_dir', sys.argv[1])\n"
            "jax.config.update("
            "'jax_persistent_cache_min_entry_size_bytes', -1)\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "from sparkdl_tpu.obs import compile_log, default_registry\n"
            "log = compile_log()\n"
            "log.arm()\n"
            "def cached_program(p, x):\n"
            "    return {'y': x['a'] * 2.0 + 1.0}\n"
            "fn = log.instrument(jax.jit(cached_program), name='c.jitted')\n"
            "fn(None, {'a': np.ones((4, 4), np.float32)})\n"
            "snap = default_registry().snapshot()\n"
            "print(json.dumps({'cache': log.events_for('c.jitted')[0].cache,\n"
            "    'hits': snap['compile.cache_hits'],\n"
            "    'requests': snap['compile.cache_requests'],\n"
            "    'writes': snap['compile.cache_writes'],\n"
            "    'read_s': snap['compile.cache_read_seconds']}))\n")
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = sanitized_cpu_env(pythonpath=root)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        runs = []
        for _ in range(2):
            out = subprocess.run(
                [sys.executable, str(script), str(tmp_path / "cache")],
                env=env, capture_output=True, text=True, timeout=240)
            assert out.returncode == 0, out.stderr[-2000:]
            runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
        first, second = runs
        assert first["cache"] == "miss" and first["hits"] == 0
        assert first["writes"] == 1 and first["requests"] == 1
        assert second["cache"] == "hit" and second["hits"] == 1
        assert second["writes"] == 0 and second["requests"] == 1
        assert second["read_s"] > 0.0


class TestPlacementIsTimedAlways:
    def test_disarmed_a_placement_feeds_the_ship_counters_once(
            self, quiet_log):
        reg = default_registry()
        mf = ModelFunction(
            lambda p, x: {"y": x["input"] * p["w"]},
            {"w": np.full((4,), 2.0, np.float32)},
            {"input": ((4,), np.float32)}, name="placed_once")
        seconds = reg.counter("ship.params_place_seconds").value
        nbytes = reg.counter("ship.params_bytes").value
        events = quiet_log.events_total
        mf.device_params()
        assert reg.counter("ship.params_bytes").value == nbytes + 16
        placed = reg.counter("ship.params_place_seconds").value
        assert placed > seconds
        mf.device_params()                   # the entry is found: no clock
        assert reg.counter("ship.params_place_seconds").value == placed
        assert reg.counter("ship.params_bytes").value == nbytes + 16
        assert quiet_log.events_total == events

    def test_armed_the_transfer_event_carries_the_same_seconds(
            self, global_log):
        reg = default_registry()
        mf = ModelFunction(
            lambda p, x: {"y": x["input"] * p["w"]},
            {"w": np.full((4,), 2.0, np.float32)},
            {"input": ((4,), np.float32)}, name="placed_armed")
        seconds = reg.counter("ship.params_place_seconds").value
        mf.device_params()
        (event,) = global_log.events_for("placed_armed.device_params")
        assert event.wall_s == pytest.approx(
            reg.counter("ship.params_place_seconds").value - seconds)
        assert event.signature["bytes"] == "16"
