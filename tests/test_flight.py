"""Flight recorder + stall watchdog + telemetry surface
(sparkdl_tpu/obs/{flight,watchdog,export}.py).

The contracts pinned here, in ISSUE order: disarmed watchdog/flight
instrumentation stays in the tracer's shared-no-op regime (<10 µs per
call, no allocation); an injected dispatcher stall fires the watchdog
within its threshold, flips /healthz to 503, and produces a
self-contained bundle carrying recent spans + a registry snapshot with
``watchdog.stalls`` >= 1 + the serve queue state; recovery clears the
verdict; /metricsz renders valid Prometheus text with kinds preserved;
SIGUSR2 and dispatch-failure triggers dump; everything degrades
gracefully (no backend, no signal) and survives cloudpickle.
"""

import json
import os
import re
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry, tracer
from sparkdl_tpu.obs import flight, watchdog
from sparkdl_tpu.obs.export import (
    TelemetryServer,
    prom_name,
    render_prometheus,
)
from sparkdl_tpu.obs.registry import MetricsRegistry
from sparkdl_tpu.obs.watchdog import StallWatchdog
from sparkdl_tpu.serve import ModelServer, ServeConfig


def _get(url: str):
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _wait_for(predicate, timeout=10.0, what="condition"):
    deadline = time.perf_counter() + timeout
    while not predicate():
        assert time.perf_counter() < deadline, f"timed out on {what}"
        time.sleep(0.01)


def _blocking_host_model(gate: threading.Event,
                         name: str = "wedge") -> ModelFunction:
    """A host-backend model whose apply blocks on ``gate`` — the
    synthetic stall: the serve dispatcher wedges INSIDE a dispatch,
    the silent-hang shape of the collective-launch deadlock."""

    def blocked_apply(params, inputs):
        gate.wait()
        return {"y": np.asarray(inputs["x"], np.float32) * 2.0}

    return ModelFunction(blocked_apply, None,
                         input_signature={"x": ((2,), np.float32)},
                         output_names=["y"], backend="host", name=name)


@pytest.fixture()
def armed_singleton_watchdog():
    """The process-wide watchdog armed with a test-speed threshold and
    restored afterwards (other tests must see it disarmed)."""
    wd = watchdog.watchdog()
    wd.arm(threshold_s=0.2)
    yield wd
    wd.disarm()
    wd._threshold_override = None


@pytest.fixture()
def flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("SPARKDL_TPU_FLIGHT_DIR", str(tmp_path))
    return tmp_path


# ---------------------------------------------------------------------------
# watchdog core


class TestWatchdog:
    def test_disarmed_watch_is_shared_noop(self, monkeypatch):
        monkeypatch.delenv("SPARKDL_TPU_WATCHDOG", raising=False)
        wd = watchdog.watchdog()
        assert not wd.armed
        # one shared object back for every disarmed call — no
        # allocation, no tracking
        assert watchdog.watch("a") is watchdog.watch("b")
        watchdog.pulse("a")     # ignored, no entry created
        assert wd.verdict()["active_sources"] == {}

    def test_disarmed_overhead(self, monkeypatch):
        """The ISSUE's acceptance bound: disarmed heartbeats ride the
        same <10 µs/call regime the tracer's no-op span is pinned to
        (min over repeats — noise only ever adds time)."""
        monkeypatch.delenv("SPARKDL_TPU_WATCHDOG", raising=False)
        n = 20_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                watchdog.pulse("hot.loop")
                with watchdog.watch("hot.loop"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 10e-6, \
            f"disarmed pulse+watch costs {best * 1e6:.2f} µs"

    def test_stall_fires_counter_and_recovers(self):
        wd = StallWatchdog()
        wd.arm(threshold_s=0.05)
        try:
            reg = default_registry()
            before = reg.counter("watchdog.stalls").value
            with wd.watch("test.loop"):
                _wait_for(lambda: not wd.healthy(), timeout=5.0,
                          what="stall verdict")
                v = wd.verdict()
                assert v["stalled_sources"] == ["test.loop"]
                assert v["stalls_fired"] >= 1
                assert reg.counter("watchdog.stalls").value > before
                # progress resumes -> the verdict clears (no restart)
                wd.pulse("test.loop")
                _wait_for(wd.healthy, timeout=5.0, what="recovery")
            assert wd.verdict()["active_sources"] == {}
        finally:
            wd.disarm()

    def test_pulsing_loop_never_stalls(self):
        wd = StallWatchdog()
        wd.arm(threshold_s=0.1)
        try:
            with wd.watch("busy.loop"):
                end = time.perf_counter() + 0.35
                while time.perf_counter() < end:
                    wd.pulse("busy.loop")
                    time.sleep(0.01)
                assert wd.healthy()
            assert wd.stalls_fired == 0
        finally:
            wd.disarm()

    def test_idle_is_not_a_stall(self):
        """No active watch window → nothing to flag, however long the
        process sits idle (the serve dispatcher opens its window only
        after collect() returns work)."""
        wd = StallWatchdog()
        wd.arm(threshold_s=0.02)
        try:
            time.sleep(0.1)
            assert wd.healthy()
            assert wd.check_once() == []
        finally:
            wd.disarm()

    def test_end_without_armed_cleans_up(self):
        """A disarm between begin and end must not leak an active
        source into a false stall after re-arming."""
        wd = StallWatchdog()
        wd.arm(threshold_s=0.05)
        try:
            ctx = wd.watch("flip.loop")
            ctx.__enter__()
            wd.disarm()
            ctx.__exit__(None, None, None)
            wd.arm(threshold_s=0.05)
            time.sleep(0.15)
            assert wd.healthy(), wd.verdict()
        finally:
            wd.disarm()

    def test_collective_hold_feeds_watchdog(
            self, armed_singleton_watchdog):
        from sparkdl_tpu.parallel import mesh
        with mesh._COLLECTIVE_LAUNCH:
            active = watchdog.watchdog().verdict()["active_sources"]
            assert "collective.hold" in active
        active = watchdog.watchdog().verdict()["active_sources"]
        assert "collective.hold" not in active

    def test_dispatch_chunks_feeds_watchdog(
            self, armed_singleton_watchdog):
        """An offline runner.run registers (and deregisters) a
        ship-dispatch source — the batch path is covered, not just
        serving."""
        from sparkdl_tpu.runtime.runner import BatchRunner
        mf = ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                      input_shape=(3,))
        x = np.arange(24, dtype=np.float32).reshape(8, 3)
        out = BatchRunner(mf, batch_size=4).run({"input": x})
        np.testing.assert_allclose(out["output"], x * 2)
        # the window closed with the run: nothing left active
        active = watchdog.watchdog().verdict()["active_sources"]
        assert not any(s.startswith("ship.dispatch") for s in active)

    def test_env_threshold_typo_degrades(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_TPU_WATCHDOG_THRESHOLD_S", "soon")
        wd = StallWatchdog()
        assert wd.threshold_s == watchdog.DEFAULT_THRESHOLD_S

    def test_pickle_drops_runtime_state(self):
        import cloudpickle as cp
        wd = StallWatchdog()
        wd.arm(threshold_s=1.5)
        try:
            with wd.watch("here"):
                wd2 = cp.loads(cp.dumps(wd))
            assert wd2.armed
            assert wd2.threshold_s == 1.5
            # active sources are process-local and did not travel
            assert wd2.verdict()["active_sources"] == {}
        finally:
            wd.disarm()


# ---------------------------------------------------------------------------
# flight recorder


class TestFlightRecorder:
    def test_dump_bundle_is_self_contained(self, tmp_path):
        rec = flight.FlightRecorder()
        trc = tracer()
        trc.arm()
        try:
            with trc.span("work", lane="engine", rows=1):
                pass
            default_registry().counter("test.flight.counter").add(3)
            path = rec.dump(path=str(tmp_path / "bundle.json"),
                            reason="unit test")
        finally:
            trc.disarm()
            trc.arm_from_env()
            trc.clear()
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["schema"] == flight.BUNDLE_SCHEMA
        assert bundle["reason"] == "unit test"
        assert bundle["pid"] == os.getpid()
        assert bundle["span_count"] >= 1
        names = {e.get("name") for e in bundle["spans"]}
        assert "work" in names
        assert bundle["registry"]["test.flight.counter"] == 3.0
        assert "watchdog" in bundle and "healthy" in bundle["watchdog"]
        assert "platform" in bundle and "memory_stats" in bundle
        assert isinstance(bundle["serve"], list)
        assert rec.dumps == 1
        assert rec.last_dump_path == path

    def test_memory_stats_degrades_not_raises(self):
        stats = flight.memory_stats()
        assert isinstance(stats, dict)   # CPU: values may be None

    def test_record_failure_counts_but_only_dumps_armed(
            self, flight_dir):
        rec = flight.FlightRecorder()
        reg = default_registry()
        before = reg.counter("flight.failures").value
        assert rec.record_failure(RuntimeError("x"), "unit") is None
        assert reg.counter("flight.failures").value == before + 1
        rec._armed_override = True   # arm WITHOUT the signal handler
        path = rec.record_failure(RuntimeError("y"), "unit")
        assert path is not None and os.path.exists(path)
        with open(path) as f:
            assert "unit" in json.load(f)["reason"]

    def test_sigusr2_dumps(self, flight_dir):
        rec = flight.recorder()
        old_handler = signal.getsignal(signal.SIGUSR2)
        before = rec.dumps
        rec.arm()
        try:
            os.kill(os.getpid(), signal.SIGUSR2)
            _wait_for(lambda: rec.dumps > before, timeout=10.0,
                      what="SIGUSR2 dump")
            with open(rec.last_dump_path) as f:
                assert json.load(f)["reason"] == "SIGUSR2"
        finally:
            rec.disarm()
            tracer().arm_from_env()
            signal.signal(signal.SIGUSR2, old_handler)
            rec._signal_installed = False

    def test_serve_dispatch_failure_triggers_dump(self, flight_dir):
        """The unhandled-failure trigger: a dispatch that raises fails
        its futures (PR-4 contract) AND, armed, leaves a bundle naming
        the failure."""
        rec = flight.recorder()
        rec._armed_override = True
        before = rec.dumps

        def boom(params, inputs):
            raise RuntimeError("synthetic dispatch failure")

        mf = ModelFunction(boom, None,
                           input_signature={"x": ((2,), np.float32)},
                           output_names=["y"], backend="host",
                           name="boom")
        server = ModelServer(ServeConfig(max_wait_s=0.0))
        try:
            server.register("boom", mf, batch_size=4)
            fut = server.submit({"x": np.zeros((2, 2), np.float32)})
            with pytest.raises(RuntimeError, match="synthetic"):
                fut.result(timeout=10)
            _wait_for(lambda: rec.dumps > before, timeout=10.0,
                      what="failure dump")
            with open(rec.last_dump_path) as f:
                bundle = json.load(f)
            assert "serve.dispatch:boom" in bundle["reason"]
            [srv] = [s for s in bundle["serve"]
                     if "boom" in s.get("models", {})]
            assert srv["models"]["boom"]["runner"]["type"] == \
                "BatchRunner"
        finally:
            server.close()
            rec._armed_override = None

    def test_autoarm_follows_env(self, monkeypatch, flight_dir):
        rec = flight.FlightRecorder()
        monkeypatch.setattr(flight, "_RECORDER", rec)
        monkeypatch.delenv("SPARKDL_TPU_FLIGHT", raising=False)
        assert flight.autoarm() is False
        monkeypatch.setenv("SPARKDL_TPU_FLIGHT", "1")
        # ModelServer construction applies the env's side effects
        server = ModelServer()
        try:
            assert rec.armed
        finally:
            server.close()
            tracer().arm_from_env()
            tracer().clear()

    def test_pickle_travels_armedness_not_history(self):
        import cloudpickle as cp
        rec = flight.FlightRecorder()
        rec._armed_override = True
        rec.dumps = 7
        rec2 = cp.loads(cp.dumps(rec))
        assert rec2.armed
        # history travels as data; the signal handler does not
        assert rec2.dumps == 7
        assert rec2._signal_installed is False


# ---------------------------------------------------------------------------
# telemetry endpoint + prometheus rendering


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"[-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|nan|inf)$")


def _assert_valid_prometheus(text: str) -> int:
    n = 0
    for line in text.strip().splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert re.match(r"^# (TYPE|HELP) ", line), repr(line)
            continue
        assert _PROM_SAMPLE.match(line), f"bad line: {line!r}"
        n += 1
    return n


class TestPrometheusRendering:
    def test_kinds_and_names(self):
        reg = MetricsRegistry()
        reg.counter("ship.rows").add(5)
        reg.gauge("serve.queue_rows").set(3)
        res = reg.reservoir("serve.latency_seconds")
        for v in (0.1, 0.2, 0.3):
            res.observe(v)
        text = render_prometheus(reg)
        assert "# TYPE sparkdl_ship_rows counter" in text
        assert "sparkdl_ship_rows 5" in text
        assert "# TYPE sparkdl_serve_queue_rows gauge" in text
        assert "# TYPE sparkdl_serve_latency_seconds_count counter" \
            in text
        assert "sparkdl_serve_latency_seconds_p99" in text
        assert _assert_valid_prometheus(text) == 5

    def test_name_sanitization(self):
        assert prom_name("a.b-c d") == "sparkdl_a_b_c_d"

    def test_default_registry_renders_valid(self):
        default_registry().counter("flight.dumps")  # ensure non-empty
        assert _assert_valid_prometheus(
            render_prometheus(default_registry())) > 0


class TestTelemetryEndpoints:
    def test_standalone_endpoints(self):
        reg = MetricsRegistry()
        reg.counter("test.requests").add(2)
        with TelemetryServer(registry=reg) as tel:
            assert tel.port > 0
            code, body = _get(tel.url("/metricsz"))
            assert code == 200
            assert "sparkdl_test_requests 2" in body
            _assert_valid_prometheus(body)
            code, body = _get(tel.url("/healthz"))
            assert code == 200
            assert json.loads(body)["status"] == "ok"
            code, body = _get(tel.url("/statusz"))
            assert code == 200
            st = json.loads(body)
            assert st["pid"] == os.getpid()
            assert st["uptime_s"] >= 0
            assert "watchdog" in st and "flight" in st
            code, _body = _get(tel.url("/nope"))
            assert code == 404

    def test_model_server_statusz_and_close(self):
        mf = ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                      input_shape=(3,))
        server = ModelServer(ServeConfig(max_wait_s=0.0))
        server.register("m", mf, batch_size=4)
        tel = server.serve_telemetry()
        try:
            code, body = _get(tel.url("/statusz"))
            assert code == 200
            st = json.loads(body)
            [srv] = st["servers"]
            model = srv["models"]["m"]
            assert model["warmed"] is None       # not warmed yet
            assert model["queue_rows"] == 0
            assert model["chunk"] == 4
            assert model["runner"]["type"] == "BatchRunner"
            assert model["runner"]["max_inflight"] == 2
            server.warmup()
            code, body = _get(tel.url("/statusz"))
            st = json.loads(body)
            assert st["servers"][0]["models"]["m"]["warmed"] is True
            port = tel.port
        finally:
            server.close()
        # close() took the attached endpoint down with the server
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=1)

    def test_serve_telemetry_is_idempotent(self):
        server = ModelServer()
        try:
            t1 = server.serve_telemetry()
            assert server.serve_telemetry() is t1
        finally:
            server.close()


# ---------------------------------------------------------------------------
# the end-to-end injected stall (the acceptance scenario)


class TestInjectedStall:
    def test_stall_dump_health_and_recovery(
            self, flight_dir, armed_singleton_watchdog):
        rec = flight.recorder()
        rec._armed_override = True    # arm triggers; skip the signal
        trc = tracer()
        trc.arm()
        gate = threading.Event()
        server = ModelServer(ServeConfig(max_wait_s=0.0,
                                         drain_timeout_s=5.0))
        tel = None
        try:
            server.register("wedge", _blocking_host_model(gate),
                            batch_size=4)
            tel = server.serve_telemetry()
            before = rec.dumps
            fut = server.submit({"x": np.zeros((2, 2), np.float32)})
            wd = watchdog.watchdog()
            _wait_for(lambda: not wd.healthy(), what="stall verdict")

            code, body = _get(tel.url("/healthz"))
            assert code == 503, (code, body)
            health = json.loads(body)
            assert health["status"] == "stalled"
            assert any("serve.dispatcher:wedge" in s
                       for s in health["stalled_sources"]), health

            _wait_for(lambda: rec.dumps > before, what="stall dump")
            with open(rec.last_dump_path) as f:
                bundle = json.load(f)
            assert bundle["span_count"] >= 1
            assert bundle["registry"].get("watchdog.stalls", 0) >= 1
            [srv] = [s for s in bundle["serve"]
                     if "wedge" in s.get("models", {})]
            assert srv["models"]["wedge"]["chunk"] == 4
            assert "watchdog stall" in bundle["reason"]

            gate.set()
            out = fut.result(timeout=10)
            assert out["y"].shape == (2, 2)
            _wait_for(wd.healthy, what="recovery")
            code, body = _get(tel.url("/healthz"))
            assert code == 200, (code, body)
            code, body = _get(tel.url("/metricsz"))
            assert code == 200
            assert _assert_valid_prometheus(body) > 0
            assert "sparkdl_watchdog_stalls" in body
        finally:
            gate.set()
            server.close()
            if tel is not None:
                tel.close()
            rec._armed_override = None
            trc.disarm()
            trc.arm_from_env()
            trc.clear()
