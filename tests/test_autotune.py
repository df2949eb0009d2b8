"""Closed-loop infeed autotuner tests (docs/PERFORMANCE.md).

The contract under test:

* controller hysteresis — bounded single-step applies, cooldown after
  every change, a quick direction flip is REFUSED and counted as an
  oscillation, clamped proposals count clamps, trial reverts bypass
  cooldown;
* targets — ServeTarget shrinks a saturated coalesce window / grows an
  underfilled one inside its p99 budget; RechunkTarget moves only
  along its pre-warmed ladder with ZERO cold retraces
  (trace-count-pinned);
* live apply points — the engine's re-chunk cut follows a
  ``LiveBatchHint`` mid-stream with row identity and order exact
  (the satellite the autotuner's engine knob rides on);
* disarmed regime — ``poll()`` is a single armed-check, pinned <10µs
  alongside the tracer bound;
* observability — decisions/oscillations/clamps in the registry,
  controller state in flight bundles, pickle discipline.
"""

import logging
import time

import numpy as np
import pyarrow as pa
import pytest

import sparkdl_tpu.runtime.runner as rmod
from sparkdl_tpu.autotune import (
    AutotuneController,
    Knob,
    Proposal,
    RechunkTarget,
    ServeTarget,
    controller,
    poll,
)
from sparkdl_tpu.data import DataFrame
from sparkdl_tpu.data.frame import LiveBatchHint
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry
from sparkdl_tpu.runtime.runner import BatchRunner
from sparkdl_tpu.serve import ModelServer, ServeConfig
from sparkdl_tpu.serve.metrics import ServeMetrics


def _double_fn(shape=(3,)):
    return ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                    input_shape=shape)


def _ctl(**over) -> AutotuneController:
    """A standalone armed controller with no warmup window (tests
    drive deterministic step sequences)."""
    c = AutotuneController(interval_s=0.0)
    c.arm()
    c.warmup_steps = over.pop("warmup_steps", 0)
    for k, v in over.items():
        setattr(c, k, v)
    return c


# ---------------------------------------------------------------------------
# the runner's once-per-reason warning


class TestWarnOnce:
    def test_warn_once_dedupes_per_reason(self, monkeypatch, caplog):
        from sparkdl_tpu.obs import remote
        monkeypatch.setattr(rmod, "_WARNED_REASONS", set())
        # an in-process decode server of an earlier test file on this
        # worker leaves its telemetry agent behind, which would take
        # the event for a parent that does not exist
        monkeypatch.setattr(remote, "_AGENT", None)
        with caplog.at_level(logging.WARNING,
                             logger="sparkdl_tpu.runtime.runner"):
            rmod.warn_once("r1", "first %s", "reason")
            rmod.warn_once("r1", "first %s", "again")
            rmod.warn_once("r2", "second reason")
        msgs = [r.getMessage() for r in caplog.records]
        assert msgs == ["first reason", "second reason"]


# ---------------------------------------------------------------------------
# controller core


class _BoxTarget:
    """A scriptable target: pops one proposal list per step."""

    def __init__(self, lo=0, hi=10, start=5):
        self.name = "box"
        self.box = {"v": start}
        self.knob = Knob("v", lambda: self.box["v"],
                         lambda x: self.box.__setitem__("v", x),
                         lo, hi)
        self.script = []

    def knobs(self):
        return [self.knob]

    def propose(self, warming):
        return self.script.pop(0) if self.script else []

    def describe(self):
        return {"name": self.name, "knobs": [self.knob.describe()]}


class TestControllerCore:
    def test_apply_cooldown_and_counters(self):
        ctl = _ctl()
        t = ctl.attach(_BoxTarget())
        t.script = [[Proposal(t.knob, 6, "up")],
                    [Proposal(t.knob, 7, "up again")]]
        ctl.step()
        assert t.box["v"] == 6 and ctl.decisions_applied == 1
        ctl.step()     # cooldown: the second proposal is held
        assert t.box["v"] == 6 and ctl.decisions_applied == 1
        snap = default_registry().snapshot()
        assert snap.get("autotune.knob.box.v") == 6.0

    def test_quick_direction_flip_is_refused_and_counted(self):
        ctl = _ctl()
        t = ctl.attach(_BoxTarget())
        t.script = [[Proposal(t.knob, 6, "up")], [], [],
                    [Proposal(t.knob, 5, "down")]]
        before = default_registry().counter(
            "autotune.oscillations").value
        for _ in range(4):
            ctl.step()
        # the flip at step 4 (3 steps after the up) is hunting: refused
        assert t.box["v"] == 6
        assert ctl.oscillations == 1
        assert default_registry().counter(
            "autotune.oscillations").value == before + 1
        assert t.knob.frozen_for > 0

    def test_slow_reversal_is_legitimate_control(self):
        ctl = _ctl()
        t = ctl.attach(_BoxTarget())
        t.script = [[Proposal(t.knob, 6, "up")], [], [], [], [],
                    [Proposal(t.knob, 5, "down")]]
        for _ in range(6):
            ctl.step()
        assert t.box["v"] == 5          # reversal outside osc_window
        assert ctl.oscillations == 0

    def test_clamps_counted_and_bounds_hold(self):
        ctl = _ctl()
        t = ctl.attach(_BoxTarget(lo=0, hi=10, start=5))
        t.script = [[Proposal(t.knob, 20, "way up")], [], [],
                    [Proposal(t.knob, 15, "still past the bound")]]
        for _ in range(4):
            ctl.step()
        assert t.box["v"] == 10         # clamped apply
        assert ctl.clamps == 2          # moved-clamp + held-clamp
        assert ctl.decisions_applied == 1

    def test_force_revert_bypasses_cooldown(self):
        ctl = _ctl()
        t = ctl.attach(_BoxTarget())
        t.script = [[Proposal(t.knob, 6, "up")],
                    [Proposal(t.knob, 5, "revert", force=True)]]
        ctl.step()
        ctl.step()
        assert t.box["v"] == 5
        assert ctl.oscillations == 0    # reverts never count

    def test_warmup_steps_measure_only(self):
        ctl = _ctl(warmup_steps=2)
        seen = []

        class _T(_BoxTarget):
            def propose(self, warming):
                seen.append(warming)
                return ([] if warming
                        else [Proposal(self.knob, 6, "up")])

        t = ctl.attach(_T())
        for _ in range(3):
            ctl.step()
        assert seen == [True, True, False]
        assert t.box["v"] == 6

    def test_interval_paces_poll_driven_steps(self):
        ctl = AutotuneController(interval_s=3600.0)
        ctl.arm()
        ctl.attach(_BoxTarget())
        ctl.maybe_step()
        ctl.maybe_step()
        assert ctl.steps == 1           # second poll inside interval

    def test_broken_target_is_skipped_loudly(self, caplog):
        ctl = _ctl()

        class _Boom:
            name = "boom"

            def knobs(self):
                return []

            def propose(self, warming):
                raise RuntimeError("target bug")

            def describe(self):
                return {"name": "boom"}

        ctl.attach(_Boom())
        good = ctl.attach(_BoxTarget())
        good.script = [[Proposal(good.knob, 6, "up")]]
        with caplog.at_level(logging.ERROR):
            ctl.step()
        assert good.box["v"] == 6       # the healthy target still ran
        assert any("propose failed" in r.getMessage()
                   for r in caplog.records)

    def test_disarmed_poll_is_noop(self, monkeypatch):
        monkeypatch.delenv("SPARKDL_TPU_AUTOTUNE", raising=False)
        ctl = controller()
        monkeypatch.setattr(ctl, "_armed_override", None)
        steps = ctl.steps
        for _ in range(50):
            poll()
        assert ctl.steps == steps

    def test_disarmed_poll_overhead(self, monkeypatch):
        """The shared-no-op contract alongside the tracer bound: the
        hot-loop hook must cost well under 10 µs disarmed (min over
        repeats — noise only ever adds time)."""
        monkeypatch.delenv("SPARKDL_TPU_AUTOTUNE", raising=False)
        monkeypatch.setattr(controller(), "_armed_override", None)
        n = 20_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                poll()
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 10e-6, f"disarmed poll costs {best * 1e6:.2f} µs"

    def test_env_arming_and_override(self, monkeypatch):
        ctl = AutotuneController()
        monkeypatch.delenv("SPARKDL_TPU_AUTOTUNE", raising=False)
        assert not ctl.armed
        monkeypatch.setenv("SPARKDL_TPU_AUTOTUNE", "1")
        assert ctl.armed
        ctl.disarm()
        assert not ctl.armed            # override beats the env
        ctl.arm_from_env()
        assert ctl.armed
        monkeypatch.setenv("SPARKDL_TPU_AUTOTUNE_INTERVAL_S", "bogus")
        import importlib
        cmod = importlib.import_module("sparkdl_tpu.autotune.core")
        monkeypatch.setattr(cmod, "_env_interval_cache", None)
        assert ctl.interval_s == cmod.DEFAULT_INTERVAL_S  # typo degrades

    def test_controller_pickles_without_lock_or_targets(self):
        import cloudpickle

        ctl = _ctl()
        ctl.attach(_BoxTarget())
        clone = cloudpickle.loads(cloudpickle.dumps(ctl))
        assert clone.armed
        assert clone.targets() == []    # live handles are process-local
        clone.step()                    # fresh locks work


# ---------------------------------------------------------------------------
# ServeTarget


class _StubSession:
    def __init__(self, max_wait_s=0.002, default_deadline_s=None):
        self.name = "m"
        self.max_wait_s = max_wait_s
        self.metrics = ServeMetrics()
        self.config = ServeConfig(max_wait_s=max_wait_s,
                                  default_deadline_s=default_deadline_s)


class TestServeTarget:
    def _window(self, s, valid, cap, n=4):
        for _ in range(n):
            s.metrics.add_batch(valid, cap)

    def test_saturated_fill_shrinks_the_window(self):
        ctl = _ctl()
        s = _StubSession(max_wait_s=0.008)
        ctl.attach(ServeTarget(s))
        self._window(s, 8, 8)
        ctl.step()                      # baseline
        self._window(s, 8, 8)
        ctl.step()
        assert s.max_wait_s == pytest.approx(0.004)

    def test_poor_fill_grows_the_window(self):
        ctl = _ctl()
        s = _StubSession(max_wait_s=0.002)
        ctl.attach(ServeTarget(s))
        self._window(s, 2, 8)
        ctl.step()
        self._window(s, 2, 8)
        ctl.step()
        assert s.max_wait_s == pytest.approx(0.003)

    def test_deadband_holds(self):
        ctl = _ctl()
        s = _StubSession(max_wait_s=0.002)
        ctl.attach(ServeTarget(s))
        for _ in range(3):
            self._window(s, 6, 8)       # fill 0.75: inside the band
            ctl.step()
        assert s.max_wait_s == pytest.approx(0.002)
        assert ctl.decisions_applied == 0

    def test_p99_budget_blocks_growth(self):
        ctl = _ctl()
        s = _StubSession(max_wait_s=0.002, default_deadline_s=0.1)
        for _ in range(10):
            s.metrics.observe_latency(0.0499)
        ctl.attach(ServeTarget(s))
        self._window(s, 2, 8)
        ctl.step()
        self._window(s, 2, 8)
        ctl.step()                      # p99 + growth > budget/2
        assert s.max_wait_s == pytest.approx(0.002)

    def test_live_session_knob_reaches_the_dispatcher(self):
        """End-to-end: a ServeTarget shrink on a REAL session changes
        what the dispatcher passes to collect(), and /statusz reports
        the live value, not the frozen config."""
        mf = _double_fn()
        server = ModelServer(ServeConfig(max_wait_s=0.008))
        server.register("m", mf, batch_size=4, max_inflight=3)
        session = server.session()
        assert session.runner.max_inflight == 3
        ctl = _ctl()
        ctl.attach(ServeTarget(session))
        self._window(session, 4, 4)
        ctl.step()
        self._window(session, 4, 4)
        ctl.step()
        assert session.max_wait_s == pytest.approx(0.004)
        st = server.telemetry_status()
        assert st["models"]["m"]["max_wait_s"] == pytest.approx(0.004)
        assert st["models"]["m"]["runner"] == {
            "type": "BatchRunner", "max_inflight": 3, "batch_size": 4}
        out = server.submit(
            {"input": np.ones((2, 3), np.float32)}).result(timeout=30)
        np.testing.assert_allclose(out["output"], 2.0)
        server.close()


# ---------------------------------------------------------------------------
# RechunkTarget: the pre-warmed shape ladder


class TestRechunkTarget:
    def test_prewarm_traces_every_rung_then_zero_retraces(self):
        """THE ladder contract: prewarm compiles each rung once (the
        jit traces the Python fn once per shape — count those calls);
        afterwards rung moves and real runs at any warmed rung perform
        ZERO new traces."""
        traces = []

        def fn(x):
            traces.append(np.shape(x))
            return x * 2.0

        mf = ModelFunction.fromSingle(fn, None, input_shape=(3,))
        r = BatchRunner(mf, batch_size=4)
        t = RechunkTarget(r, ladder=(2, 4, 8))
        warmed = t.prewarm()
        assert warmed == 3
        assert len(traces) == 3         # one per rung
        assert t.prewarm() == 0         # idempotent
        for rung in (0, 2, 1):
            t._rung.set(rung)
            x = np.ones((10, 3), np.float32)
            np.testing.assert_allclose(r.run({"input": x})["output"],
                                       2.0)
        assert len(traces) == 3, "a rung move cold-retraced"

    def test_padding_tax_steps_the_ladder_down(self):
        ctl = _ctl()
        traces = []

        def fn(x):
            traces.append(np.shape(x))
            return x * 2.0

        mf = ModelFunction.fromSingle(fn, None, input_shape=(3,))
        r = BatchRunner(mf, batch_size=8)
        t = ctl.attach(RechunkTarget(r, ladder=(4, 8)))
        t.prewarm()
        n_warm = len(traces)
        x = np.ones((2, 3), np.float32)     # fill 2/8 < 0.5
        r.run({"input": x})
        ctl.step()                          # baseline window
        r.run({"input": x})
        ctl.step()                          # fill 0.25 → step down
        assert r.batch_size == 4
        r.run({"input": x})                 # runs at the new rung
        assert len(traces) == n_warm, "the down-rung cold-retraced"

    def test_prewarm_never_touches_the_live_batch_size(self):
        """Prewarm compiles rungs through the jit cache directly — a
        concurrent run() on another thread must never observe a
        transient rung. The traced fn itself asserts the live knob is
        untouched at every compile."""
        observed = []

        r_box = {}

        def fn(x):
            observed.append(r_box["r"].batch_size)
            return x * 2.0

        mf = ModelFunction.fromSingle(fn, None, input_shape=(3,))
        r = BatchRunner(mf, batch_size=4)
        r_box["r"] = r
        t = RechunkTarget(r, ladder=(2, 4, 8))
        assert t.prewarm() == 3
        assert observed == [4, 4, 4], observed
        assert r.batch_size == 4

    def test_attach_while_armed_prewarns_on_the_setup_thread(self):
        """controller().attach runs the ladder compile immediately
        (the on_attach hook) so it never lands inside a hot loop's
        first controller step."""
        traces = []
        mf = ModelFunction.fromSingle(
            lambda x: (traces.append(1), x * 2.0)[1], None,
            input_shape=(3,))
        r = BatchRunner(mf, batch_size=4)
        ctl = _ctl()
        t = ctl.attach(RechunkTarget(r, ladder=(2, 4)))
        assert t.warmed and len(traces) == 2

    def test_off_ladder_batch_size_rejected_at_ctor(self):
        r = BatchRunner(_double_fn(), batch_size=6)
        with pytest.raises(ValueError, match="ladder"):
            RechunkTarget(r, ladder=(4, 8))


# ---------------------------------------------------------------------------
# mid-stream hint changes through the engine (the apply point)


class _Chunky:
    """A preferred_chunk carrier for LiveBatchHint (stands in for the
    runner whose batch_size the controller moves)."""

    def __init__(self, n):
        self.batch_size = n

    @property
    def preferred_chunk(self):
        return self.batch_size


class TestMidStreamHintChange:
    def test_live_hint_moves_between_blocks_rows_exact(self):
        """The satellite pin: when the hint moves between blocks the
        partition-spanning re-slice stays row-exact and ordered — and
        the cut actually follows the new hint."""
        chunky = _Chunky(8)
        hint = LiveBatchHint(chunky)
        assert int(hint) == 8 and bool(hint)
        seen = []

        def fn(batch):
            seen.append(batch.num_rows)
            if len(seen) == 1:
                chunky.batch_size = 4   # the controller's apply point
            return batch

        ids = np.arange(30)
        df = DataFrame.from_table(pa.table({"id": ids}), 6)
        out = df.map_batches(fn, kind="device", name="dev",
                             batch_hint=hint).collect()
        np.testing.assert_array_equal(
            out.column("id").to_numpy(zero_copy_only=False), ids)
        # the first cut honored hint 8; later cuts honored hint 4
        assert seen[0] == 8, seen
        assert any(n == 4 for n in seen[1:]), seen
        # every dispatched block after the move is ≤ the larger hint
        assert sum(seen) == 30

    def test_hint_shrink_and_regrow_stays_ordered(self):
        """Hint moves in BOTH directions mid-stream (shrink then grow
        back) keep row order across partition-spanning blocks."""
        chunky = _Chunky(6)
        seen = []

        def fn(batch):
            seen.append(batch.num_rows)
            if len(seen) == 1:
                chunky.batch_size = 3
            elif len(seen) == 3:
                chunky.batch_size = 12
            return batch

        ids = np.arange(40)
        df = DataFrame.from_table(pa.table({"id": ids}), 8)
        out = df.map_batches(fn, kind="device", name="dev",
                             batch_hint=LiveBatchHint(chunky)).collect()
        np.testing.assert_array_equal(
            out.column("id").to_numpy(zero_copy_only=False), ids)
        assert sum(seen) == 40

    def test_live_hint_pickles_with_its_runner(self):
        import cloudpickle

        hint = LiveBatchHint(_Chunky(16))
        clone = cloudpickle.loads(cloudpickle.dumps(hint))
        assert int(clone) == 16

    def test_tensor_transformer_publishes_live_hint(self):
        """The production path: TensorTransformer's device stage hint
        follows the runner's batch size live."""
        from sparkdl_tpu.transformers.tensor_transform import (
            TensorTransformer,
        )

        mf = _double_fn((4,))
        t = TensorTransformer(modelFunction=mf,
                              inputMapping={"x": "input"},
                              outputMapping={"output": "y"},
                              batchSize=8)
        x = np.ones((12, 4), np.float32)
        df = DataFrame.from_table(pa.table({"i": np.arange(12)}), 2) \
            .with_column("x", lambda b, x=x: x[:b.num_rows])
        plan_df = t.transform(df)
        stage = next(st for st in plan_df._plan if st.kind == "device")
        assert isinstance(stage.batch_hint, LiveBatchHint)
        assert int(stage.batch_hint) == 8
        out = plan_df.collect()
        assert out.num_rows == 12


# ---------------------------------------------------------------------------
# observability plumbing


class TestObservability:
    def test_flight_bundle_carries_controller_state(self):
        from sparkdl_tpu.obs.flight import FlightRecorder

        ctl = controller()
        try:
            ctl.attach(_BoxTarget())
            bundle = FlightRecorder().bundle(reason="test")
            at = bundle["autotune"]
            assert "armed" in at and "decisions" in at
            assert any(t.get("name") == "box" for t in at["targets"])
        finally:
            ctl.reset()

    def test_apply_lands_on_the_autotune_lane(self):
        from sparkdl_tpu.obs import Tracer

        t = Tracer(capacity=64)
        ctl = _ctl()
        box = ctl.attach(_BoxTarget())
        box.script = [[Proposal(box.knob, 6, "up")]]
        import importlib
        cmod = importlib.import_module("sparkdl_tpu.autotune.core")
        real_span = cmod.span

        def spy_span(name, lane="host", **attrs):
            return t.span(name, lane=lane, **attrs)

        cmod.span = spy_span
        try:
            t.arm()
            ctl.step()
        finally:
            cmod.span = real_span
        lanes = {s.lane for s in t.spans()}
        names = {s.name for s in t.spans()}
        assert lanes == {"autotune"}
        assert {"autotune.step", "autotune.apply"} <= names

    def test_state_reports_knobs_and_counters(self):
        ctl = _ctl()
        box = ctl.attach(_BoxTarget())
        box.script = [[Proposal(box.knob, 6, "up")]]
        ctl.step()
        st = ctl.state()
        assert st["decisions"] == 1 and st["oscillations"] == 0
        (tgt,) = st["targets"]
        assert tgt["knobs"][0]["value"] == 6
