"""Unified pipeline tracing (sparkdl_tpu/obs): span tracer, metrics
registry, Perfetto export, instrumentation, lint + pickle discipline.

The contracts pinned here, in ISSUE order: a disarmed tracer is a
true no-op (no ring growth, per-call cost far under 1% of a tight
stage call), an armed 2-thread concurrent transform yields properly
nested same-thread spans and a valid Perfetto export, the
collective-launch counters move under racing fitMultiple trials, the
ring buffer caps with a visible drop counter, arming introduces zero
new unsuppressed lint findings, and tracer/registry survive
cloudpickle with remote-side spans staying remote."""

import json
import threading
import time

import numpy as np
import pytest

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import (
    MetricsRegistry,
    Tracer,
    default_registry,
    span,
    tracer,
)
from sparkdl_tpu.obs.report import load_events, summarize
from sparkdl_tpu.runtime.runner import BatchRunner, RunnerMetrics

# fixtures reused from the estimator suite (tiny keras model + the
# brightness-labeled image frame); `tests` resolves as a namespace
# package from the repo root
from tests.test_estimators import (  # noqa: F401
    keras_cls_file,
    uri_label_df,
)


def _mf(width=3):
    return ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                    input_shape=(width,))


@pytest.fixture()
def armed_tracer(monkeypatch):
    """The global tracer, armed via the env (as production would) and
    cleared before/after so tests don't see each other's spans."""
    t = tracer()
    monkeypatch.setenv("SPARKDL_TPU_TRACE", "1")
    t.clear()
    yield t
    t.clear()


# ---------------------------------------------------------------------------
# tracer core


class TestTracerCore:
    def test_disarmed_is_noop_no_ring_growth(self, monkeypatch):
        monkeypatch.delenv("SPARKDL_TPU_TRACE", raising=False)
        t = Tracer(capacity=16)
        for _ in range(100):
            with t.span("work", lane="engine", rows=1):
                pass
        assert t.spans() == []
        assert t.dropped == 0
        # the module-level fast path allocates nothing: one shared
        # no-op object comes back for every disarmed call
        tracer().clear()
        assert span("a") is span("b")

    def test_disarmed_span_overhead(self, monkeypatch):
        """The <1%-on-a-tight-stage-loop contract: engine stage calls
        are ≥ 1 ms (decode/resize/device dispatch granularity), so the
        disarmed span wrapping each one must cost well under 10 µs.
        Measured as the min over repeats (robust to CI noise — noise
        only ever adds time)."""
        monkeypatch.delenv("SPARKDL_TPU_TRACE", raising=False)
        n = 20_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with span("s", lane="engine"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 10e-6, f"disarmed span costs {best * 1e6:.2f} µs"

    def test_armed_records_thread_and_attrs(self):
        t = Tracer(capacity=16)
        t.arm()
        with t.span("work", lane="ship", rows=4):
            time.sleep(0.001)
        (rec,) = t.spans()
        assert rec.name == "work"
        assert rec.lane == "ship"
        assert rec.attrs == {"rows": 4}
        assert rec.thread_id == threading.get_ident()
        assert rec.end - rec.start >= 0.001

    def test_env_arming_and_override(self, monkeypatch):
        t = Tracer(capacity=4)
        monkeypatch.delenv("SPARKDL_TPU_TRACE", raising=False)
        assert not t.armed
        monkeypatch.setenv("SPARKDL_TPU_TRACE", "1")
        assert t.armed
        t.disarm()  # programmatic override beats the env
        assert not t.armed
        t.arm_from_env()
        assert t.armed
        monkeypatch.delenv("SPARKDL_TPU_TRACE", raising=False)
        t.arm()
        assert t.armed

    def test_ring_buffer_caps_and_notes_drop(self):
        """Old spans evict, the drop counter says so, and the export
        carries a visible note — no silent truncation."""
        t = Tracer(capacity=8)
        t.arm()
        for i in range(20):
            with t.span(f"s{i}", lane="engine"):
                pass
        recs = t.spans()
        assert len(recs) == 8
        assert [r.name for r in recs] == [f"s{i}" for i in range(12, 20)]
        assert t.dropped == 12
        note = [e for e in t.trace_events()
                if "dropped" in str(e.get("name", ""))]
        assert note and note[0]["args"]["dropped"] == 12

    def test_exception_exit_still_records(self):
        t = Tracer(capacity=4)
        t.arm()
        with pytest.raises(ValueError):
            with t.span("boom", lane="engine"):
                raise ValueError("x")
        (rec,) = t.spans()
        assert rec.attrs["error"] == "ValueError"

    def test_garbage_buffer_env_degrades_to_default(self, monkeypatch):
        """A tracing-config typo must not make the library
        unimportable (the singleton parses the env at import time) —
        it falls back to the default capacity with a warning."""
        from sparkdl_tpu.obs.trace import DEFAULT_CAPACITY
        for bad in ("0", "-5", "64k", "  "):
            monkeypatch.setenv("SPARKDL_TPU_TRACE_BUFFER", bad)
            assert Tracer().capacity == DEFAULT_CAPACITY, bad
        monkeypatch.setenv("SPARKDL_TPU_TRACE_BUFFER", "128")
        assert Tracer().capacity == 128
        # an EXPLICIT bad ctor arg still fails loudly
        with pytest.raises(ValueError):
            Tracer(capacity=0)

    def test_clear_resets_spans_and_drop_counter(self):
        t = Tracer(capacity=2)
        t.arm()
        for _ in range(5):
            with t.span("s"):
                pass
        assert t.dropped == 3
        t.clear()
        assert t.spans() == [] and t.dropped == 0


# ---------------------------------------------------------------------------
# armed concurrent transform → nested spans + valid Perfetto export


class TestConcurrentTransform:
    def test_two_thread_transform_spans_and_export(self, armed_tracer,
                                                   tmp_path):
        runner = BatchRunner(_mf(), batch_size=4)
        x = np.arange(48, dtype=np.float32).reshape(16, 3)
        errs = []

        def work():
            try:
                out = runner.run({"input": x})
                np.testing.assert_allclose(out["output"], x * 2)
            except Exception as e:  # pragma: no cover - assertion aid
                errs.append(e)

        threads = [threading.Thread(target=work) for _ in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs
        recs = armed_tracer.spans()
        assert {r.lane for r in recs} >= {"ship", "device"}
        # both worker threads recorded
        assert len({r.thread_id for r in recs}) >= 2
        # same-thread spans follow stack discipline: any two either
        # don't overlap or one contains the other (never a partial
        # overlap — that would mean a corrupted/racing timeline)
        by_thread = {}
        for r in recs:
            by_thread.setdefault(r.thread_id, []).append(r)
        for spans_ in by_thread.values():
            spans_.sort(key=lambda r: (r.start, -r.end))
            for a, b in zip(spans_, spans_[1:]):
                assert b.start >= a.end or b.end <= a.end + 1e-9, \
                    (a, b)

        path = tmp_path / "trace.json"
        n = armed_tracer.export(str(path))
        events = json.loads(path.read_text())
        assert isinstance(events, list)
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == n == len(recs)
        for e in xs:
            for k in ("ts", "dur", "pid", "tid", "name", "args"):
                assert k in e
        # every span's pid resolves to a named lane process
        named = {e["pid"] for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert {e["pid"] for e in xs} <= named

    def test_engine_lane_from_dataframe_pipeline(self, armed_tracer):
        from sparkdl_tpu.data import DataFrame
        df = DataFrame.from_pylist(
            [{"x": float(i)} for i in range(12)], num_partitions=3)
        df.map_batches(lambda b: b, name="noop").collect()
        recs = armed_tracer.spans()
        assert any(r.lane == "engine" and r.name == "stage:noop"
                   for r in recs)
        assert any(r.name == "source.load" for r in recs)


# ---------------------------------------------------------------------------
# registry


class TestRegistry:
    def test_counter_is_thread_safe(self):
        reg = MetricsRegistry()
        c = reg.counter("t.hits")

        def bump():
            for _ in range(10_000):
                c.add()

        threads = [threading.Thread(target=bump) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert reg.snapshot()["t.hits"] == 40_000

    def test_gauge_set_and_set_max(self):
        reg = MetricsRegistry()
        g = reg.gauge("t.depth")
        g.set(3)
        g.set(1)
        assert reg.snapshot()["t.depth"] == 1.0
        g.set_max(5)
        g.set_max(2)
        assert reg.snapshot()["t.depth"] == 5.0

    def test_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("t.x")
        with pytest.raises(TypeError, match="Counter"):
            reg.gauge("t.x")

    def test_snapshot_is_flat_and_sorted(self):
        reg = MetricsRegistry()
        reg.counter("b").add(2)
        reg.gauge("a").set(1)
        assert list(reg.snapshot()) == ["a", "b"]

    def test_default_registry_is_process_wide(self):
        assert default_registry() is default_registry()

    def test_queue_depth_gauges_from_runner(self):
        BatchRunner(_mf(), batch_size=4).run(
            {"input": np.arange(36, dtype=np.float32).reshape(12, 3)})
        snap = default_registry().snapshot()
        assert snap["ship.inflight"] == 0.0  # fully drained
        assert snap["ship.inflight_peak"] >= 1.0

    def test_reservoir_quantiles_and_snapshot_keys(self):
        reg = MetricsRegistry()
        r = reg.reservoir("t.latency")
        assert r.quantile(0.5) == 0.0    # empty never raises
        for v in range(1, 101):
            r.observe(float(v))
        assert r.quantile(0.5) == 50.0
        assert r.quantile(0.99) == 99.0
        assert r.quantile(1.0) == 100.0
        snap = reg.snapshot()
        # reservoirs flatten to derived keys, one level deep
        assert snap["t.latency.count"] == 100.0
        assert snap["t.latency.p50"] == 50.0
        assert snap["t.latency.p99"] == 99.0
        with pytest.raises(ValueError, match="quantile"):
            r.quantile(1.5)

    def test_reservoir_window_bounded_count_lifetime(self):
        from sparkdl_tpu.obs import Reservoir
        r = Reservoir("t.win", capacity=4)
        for v in range(10):
            r.observe(float(v))
        assert r.count == 10               # lifetime total
        assert r.quantile(0.0) == 6.0      # window kept the newest 4
        with pytest.raises(ValueError, match="capacity"):
            Reservoir("t.bad", capacity=0)

    def test_reservoir_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.reservoir("t.r")
        with pytest.raises(TypeError, match="Reservoir"):
            reg.counter("t.r")
        reg.gauge("t.g")
        with pytest.raises(TypeError, match="Gauge"):
            reg.reservoir("t.g")

    def test_reservoir_round_trip_keeps_window(self):
        import pickle

        from sparkdl_tpu.obs import Reservoir
        r = Reservoir("t.p")
        r.observe(1.0)
        r.observe(3.0)
        r2 = pickle.loads(pickle.dumps(r))
        assert r2.count == 2 and r2.quantile(1.0) == 3.0
        r2.observe(5.0)                    # lock recreated, still works
        assert r2.quantile(1.0) == 5.0


# ---------------------------------------------------------------------------
# collective launch observability


class TestCollectiveLaunchObservability:
    def test_contended_acquire_counts_and_spans(self, armed_tracer):
        import jax

        from sparkdl_tpu.parallel import mesh as mesh_mod
        from sparkdl_tpu.parallel.mesh import collective_launch, make_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device")
        launch = collective_launch(make_mesh())
        reg = default_registry()
        waits0 = reg.counter("collective.lock_waits").value
        wait_s0 = reg.counter("collective.lock_wait_seconds").value

        # deterministic contention: hold the real lock while a second
        # thread enters the instrumented wrapper
        mesh_mod._COLLECTIVE_LAUNCH_LOCK.acquire()
        entered = threading.Event()

        def contend():
            entered.set()
            with launch:
                pass

        th = threading.Thread(target=contend)
        th.start()
        entered.wait()
        time.sleep(0.05)
        mesh_mod._COLLECTIVE_LAUNCH_LOCK.release()
        th.join()

        assert reg.counter("collective.lock_waits").value == waits0 + 1
        assert reg.counter("collective.lock_wait_seconds").value \
            >= wait_s0 + 0.04
        recs = [r for r in armed_tracer.spans()
                if r.name == "collective_lock_wait"]
        assert recs and recs[-1].attrs["contended"] is True
        assert recs[-1].end - recs[-1].start >= 0.04

    def test_enter_failure_releases_the_launch_lock(self, monkeypatch):
        """An exception inside __enter__ AFTER the lock is acquired
        (e.g. a registry kind collision) must release it — __exit__
        never runs when __enter__ raises, and a leaked hold would
        deadlock every future collective launch."""
        import jax

        from sparkdl_tpu.parallel import mesh as mesh_mod
        from sparkdl_tpu.parallel.mesh import collective_launch, make_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device")

        def boom():
            raise RuntimeError("registry unavailable")

        monkeypatch.setattr(mesh_mod, "default_registry", boom)
        with pytest.raises(RuntimeError, match="registry unavailable"):
            with collective_launch(make_mesh()):
                pass  # pragma: no cover - never reached
        assert not mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()
        monkeypatch.undo()
        with collective_launch(make_mesh()):  # still usable afterwards
            assert mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()

    def test_racing_fit_multiple_trials_increment_counters(
            self, keras_cls_file, uri_label_df):
        """Two fitMultiple trials racing their mesh-jitted train steps
        must leave their launch serialization visible in the registry:
        every step's dispatch counts a launch and its acquire time
        lands in collective.lock_wait_seconds."""
        from tests.test_estimators import make_estimator

        reg = default_registry()
        launches0 = reg.counter("collective.launches").value
        wait0 = reg.counter("collective.lock_wait_seconds").value
        est = make_estimator(keras_cls_file, parallelism=2)
        grid = [
            {est.getParam("kerasFitParams"):
             {"epochs": 1, "batch_size": 8, "learning_rate": 1e-4,
              "seed": 1}},
            {est.getParam("kerasFitParams"):
             {"epochs": 2, "batch_size": 8, "learning_rate": 0.05,
              "seed": 1}},
        ]
        got = dict(est.fitMultiple(uri_label_df, grid))
        assert set(got) == {0, 1}
        # 20 images, global batch rounded to the 8-device data axis →
        # ≥1 step per epoch per trial, 3 epochs total
        assert reg.counter("collective.launches").value >= launches0 + 3
        assert reg.counter("collective.lock_wait_seconds").value > wait0


# ---------------------------------------------------------------------------
# estimator + sanitizer instrumentation


class TestEstimatorAndSanitizerInstrumentation:
    def test_logistic_regression_estimator_lane(self, armed_tracer):
        import pyarrow as pa

        from sparkdl_tpu.data import DataFrame
        from sparkdl_tpu.data.tensors import append_tensor_column
        from sparkdl_tpu.estimators import LogisticRegression
        rng = np.random.default_rng(0)
        y = rng.integers(0, 2, 24)
        X = rng.normal(0, 1, (24, 4)).astype(np.float32) \
            + 3.0 * y[:, None]
        b = pa.RecordBatch.from_pylist([{"label": int(v)} for v in y])
        b = append_tensor_column(b, "features", X)
        LogisticRegression(maxIter=3).fit(DataFrame.from_batches([b]))
        recs = armed_tracer.spans()
        assert any(r.lane == "estimator" and r.name == "step"
                   for r in recs)

    def test_sanitizer_arm_counts_into_registry(self, monkeypatch):
        reg = default_registry()
        armed0 = reg.counter("sanitize.armed_runs").value
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        BatchRunner(_mf(), batch_size=4).run(
            {"input": np.arange(24, dtype=np.float32).reshape(8, 3)})
        assert reg.counter("sanitize.armed_runs").value > armed0


# ---------------------------------------------------------------------------
# throughput_report routes through the registry (PR-1 counters included)


class TestThroughputReportRouting:
    def test_device_line_carries_copy_counters(self):
        from sparkdl_tpu.utils import StageMetrics, throughput_report
        sm = StageMetrics()
        sm.add("decode", 1.0, 100)
        rm = RunnerMetrics()
        rm.add(100, 2, 0.5, bytes_staged=4096, bytes_copied=128,
               transfer_wait_seconds=0.25)
        rep = throughput_report(sm, rm)
        assert "decode" in rep
        assert "4096 B staged" in rep
        assert "128 B copied" in rep
        assert "0.250s transfer wait" in rep

    def test_report_renders_from_registry_snapshot(self):
        from sparkdl_tpu.utils import StageMetrics, throughput_report
        sm = StageMetrics()
        sm.add("resize", 2.0, 10)
        rm = RunnerMetrics()
        rm.add(10, 1, 1.0, bytes_staged=7)
        reg = MetricsRegistry()
        rep = throughput_report(sm, rm, registry=reg)
        snap = reg.snapshot()
        assert snap["engine.stage.resize.rows"] == 10
        assert snap["ship.bytes_staged"] == 7
        assert "resize" in rep and "7 B staged" in rep

    def test_reused_registry_does_not_leak_stale_stages(self):
        """A reused registry (the default_registry routing) keeps
        gauges from earlier runs — a later report must list only the
        stages ITS StageMetrics actually ran."""
        from sparkdl_tpu.utils import StageMetrics, throughput_report
        reg = MetricsRegistry()
        run1 = StageMetrics()
        # a stage name that is no lane of the ledger: the report's last
        # line names the lane that binds the PROCESS ("bound by:
        # decode" after a decode-heavy test file on this worker)
        run1.add("thumbnail", 1.0, 5)
        throughput_report(run1, registry=reg)
        run2 = StageMetrics()
        run2.add("pack", 1.0, 5)
        rep2 = throughput_report(run2, registry=reg)
        assert "pack" in rep2
        assert "thumbnail" not in rep2


# ---------------------------------------------------------------------------
# lint discipline


class TestLintDiscipline:
    def test_armed_tracer_zero_new_unsuppressed_findings(self,
                                                         monkeypatch):
        """Arming is a runtime switch; the instrumented code is always
        there — the analyzer must stay at zero unsuppressed either
        way."""
        import os

        from sparkdl_tpu.analysis.walker import analyze_paths
        monkeypatch.setenv("SPARKDL_TPU_TRACE", "1")
        import sparkdl_tpu
        pkg = os.path.dirname(sparkdl_tpu.__file__)
        unsuppressed = [f for f in analyze_paths([pkg])
                        if not f.suppressed]
        assert unsuppressed == [], [f.render() for f in unsuppressed]

    def test_obs_drain_is_allowlisted_not_invisible(self):
        import os

        import sparkdl_tpu
        from sparkdl_tpu.analysis.walker import analyze_paths
        pkg = os.path.dirname(sparkdl_tpu.__file__)
        found = analyze_paths([os.path.join(pkg, "obs")])
        h1 = [f for f in found if f.rule == "H1"]
        assert any(f.suppressed and f.qualname == "timed_device_get"
                   for f in h1)

    def test_h2_flags_span_inside_jit(self):
        """Spans read the host wall clock — inside a jit-traced
        function that happens once, at trace time (H2)."""
        from sparkdl_tpu.analysis.walker import analyze_source
        src = (
            "import jax\n"
            "from sparkdl_tpu.obs import span\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    with span('bad', lane='ship'):\n"
            "        return x * 2\n")
        findings = analyze_source(src, "fixture.py", rules=["H2"])
        assert any("span" in f.message and not f.suppressed
                   for f in findings)
        # outside the jit: clean
        ok = (
            "from sparkdl_tpu.obs import span\n"
            "def g(x):\n"
            "    with span('fine'):\n"
            "        return x * 2\n")
        assert analyze_source(ok, "fixture.py", rules=["H2"]) == []


# ---------------------------------------------------------------------------
# pickle discipline (StageMetrics precedent)


class TestPickleDiscipline:
    def test_tracer_round_trip_drops_spans_keeps_config(self):
        import cloudpickle as cp
        t = Tracer(capacity=32)
        t.arm()
        with t.span("local", lane="engine"):
            pass
        t2 = cp.loads(cp.dumps(t))
        # remote-side spans stay remote: the buffer does not travel
        assert t2.spans() == []
        assert t2.dropped == 0
        assert t2.capacity == 32
        assert t2.armed  # the programmatic arm travels
        with t2.span("remote", lane="engine"):
            pass
        assert [r.name for r in t2.spans()] == ["remote"]
        # and the original is untouched
        assert [r.name for r in t.spans()] == ["local"]
        # the clock origin is per-process (perf_counter): the restored
        # tracer re-anchors its epoch, so exported timestamps are
        # sane relative offsets, not sender-minus-receiver garbage
        (ev,) = [e for e in t2.trace_events() if e["ph"] == "X"]
        assert 0 <= ev["ts"] < 60 * 1e6

    def test_registry_round_trip_keeps_values(self):
        import cloudpickle as cp
        reg = MetricsRegistry()
        reg.counter("c").add(5)
        reg.gauge("g").set(2)
        reg2 = cp.loads(cp.dumps(reg))
        assert reg2.snapshot() == {"c": 5.0, "g": 2.0}
        reg2.counter("c").add(1)  # lock recreated, still usable
        assert reg2.snapshot()["c"] == 6.0

    def test_collective_launch_wrapper_ships_as_singleton(self):
        """A closure capturing the launch wrapper must survive the
        wire: the wrapped lock doesn't pickle, so __reduce__ re-binds
        to the receiving process's singleton (H3 discipline in
        identity-preserving form)."""
        import cloudpickle as cp
        import jax

        from sparkdl_tpu.parallel import mesh as mesh_mod
        from sparkdl_tpu.parallel.mesh import collective_launch, make_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device")
        launch = collective_launch(make_mesh())
        launch2 = cp.loads(cp.dumps(launch))
        assert launch2 is mesh_mod._COLLECTIVE_LAUNCH
        with launch2:
            assert mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()
        assert not mesh_mod._COLLECTIVE_LAUNCH_LOCK.locked()

    def test_instrumented_runner_still_ships(self):
        """The obs imports must not break the runner's existing wire
        discipline (H3: stage closures ship with cloudpickle)."""
        import cloudpickle as cp
        r = cp.loads(cp.dumps(BatchRunner(_mf(), batch_size=4)))
        x = np.arange(12, dtype=np.float32).reshape(4, 3)
        np.testing.assert_allclose(r.run({"input": x})["output"], x * 2)


# ---------------------------------------------------------------------------
# report CLI


class TestReportCLI:
    def _export(self, tmp_path):
        t = Tracer(capacity=64)
        t.arm()
        with t.span("stage:decode", lane="engine", rows=8):
            time.sleep(0.002)
        with t.span("dispatch", lane="ship", rows=8):
            time.sleep(0.001)
        with t.span("device_get", lane="device"):
            time.sleep(0.001)
        path = str(tmp_path / "t.json")
        t.export(path)
        return path

    def test_summary_has_lanes_and_stalls(self, tmp_path):
        out = summarize(load_events(self._export(tmp_path)))
        for needle in ("engine", "ship", "device", "busy%",
                       "device/device_get"):
            assert needle in out, out

    def test_cli_entry_point(self, tmp_path):
        import subprocess
        import sys
        path = self._export(tmp_path)
        proc = subprocess.run(
            [sys.executable, "-m", "sparkdl_tpu.obs", "report", path],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "busy%" in proc.stdout

    def test_cli_rejects_garbage(self, tmp_path):
        from sparkdl_tpu.obs.report import main
        bad = tmp_path / "bad.json"
        bad.write_text("{\"notTraceEvents\": 1}")
        assert main(["report", str(bad)]) == 2
        assert main(["wrong"]) == 2


# ---------------------------------------------------------------------------
# report forward-compat: lanes are data, not a schema


class TestReportForwardCompat:
    """An older report invocation must summarize traces carrying lanes
    it has never heard of, and a newer report must tolerate traces
    from before those lanes existed — the lane set grows every obs PR
    (serve in PR 4, obs/flight in this one) and neither direction may
    crash."""

    def test_unknown_lane_summarizes(self):
        events = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "lane-from-the-future"}},
            {"name": "mystery", "cat": "lane-from-the-future",
             "ph": "X", "ts": 0.0, "dur": 50.0, "pid": 1, "tid": 7,
             "args": {}},
        ]
        out = summarize(events)
        assert "lane-from-the-future" in out
        assert "mystery" in out

    def test_span_without_lane_metadata_falls_back_to_cat(self):
        events = [{"name": "orphan", "cat": "obs", "ph": "X",
                   "ts": 0.0, "dur": 10.0, "pid": 99, "tid": 1,
                   "args": {}}]
        out = summarize(events)
        assert "obs/orphan" in out

    def test_zero_span_lane_does_not_crash_or_render_busy(self):
        """Lane metadata with no spans (an armed run that never
        exercised a subsystem) must not crash the report or appear as
        a busy lane."""
        events = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "serve"}},
            {"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
             "args": {"name": "engine"}},
            {"name": "stage:decode", "cat": "engine", "ph": "X",
             "ts": 0.0, "dur": 25.0, "pid": 2, "tid": 1, "args": {}},
        ]
        out = summarize(events)
        assert "engine" in out
        # the empty lane contributes no busy line
        assert "serve  " not in out.split("top spans")[0].replace(
            "lanes", "")

    def test_malformed_metadata_and_missing_dur_tolerated(self):
        events = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0},
            {"name": "short", "ph": "X", "ts": 1.0, "pid": 1,
             "tid": 1},
        ]
        out = summarize(events)
        assert "short" in out

    def test_all_metadata_no_spans(self):
        events = [{"name": "process_name", "ph": "M", "pid": 1,
                   "tid": 0, "args": {"name": "engine"}}]
        assert summarize(events) == "(no spans in trace)"

    def test_new_obs_lane_flows_through_report(self, tmp_path):
        """The flight recorder's own dump span (obs lane, new in this
        PR) must ride the generic machinery like every other lane."""
        from sparkdl_tpu.obs import flight
        t = tracer()
        t.arm()
        try:
            rec = flight.FlightRecorder()
            # a dump's own span records at its END — the SECOND
            # bundle carries the first dump's span
            rec.dump(path=str(tmp_path / "a.json"), reason="first")
            path = rec.dump(path=str(tmp_path / "b.json"),
                            reason="report test")
        finally:
            t.disarm()
            t.arm_from_env()
        with open(path) as f:
            events = json.load(f)["spans"]
        t.clear()
        out = summarize(events)
        assert "obs" in out
        assert "flight.dump" in out
