"""Closed-loop infeed autotuner: the measure→decide→apply controller.

The throughput knobs that are not fixed by a measurement on the chip
used to be hand-frozen: the engine re-chunk hint, the serve coalesce
window, the decode pool's width and a fleet model's replica count
were static config — while the process continuously measured the
signals needed to set them (batch fill and padding, serve fill ratio
and p99, the live roofline's verdict). This module closes the loop, the tf.data lesson (Murray et
al., 2021: autotuned pipeline parallelism/prefetch beats static expert
configs across heterogeneous hosts) applied to a link whose bandwidth
swings several-x between minutes.

Shape of the loop:

* **measure** — attached targets (:mod:`sparkdl_tpu.autotune.targets`)
  diff the per-object metrics the pipeline already keeps
  (``RunnerMetrics``, ``ServeMetrics``) into per-window rates; nothing
  new is sampled on the hot path.
* **decide** — targets emit bounded single-step :class:`Proposal`\\ s
  (one rung / ±1 / one multiplicative notch) gated by hysteresis: a
  per-knob cooldown after every change, an explore→evaluate→revert
  trial for speculative moves, and a freeze after a reverted trial so
  a knob that didn't pay stops being poked. A quick direction flip is
  counted as an oscillation (``autotune.oscillations``), refused, and
  backed off — the controller must settle, not hunt.
* **apply** — knob writes are single int/float attribute stores that
  the owning hot loop re-reads at its next unit of work
  (``runner.run`` snapshots ``batch_size`` per call, the serve
  dispatcher reads ``max_wait_s`` per collect, the engine re-reads the
  re-chunk hint per block) — so applies never interrupt a dispatch,
  never hold a hot-path lock, and are watchdog-safe by construction.
  Shape-changing knobs move only along a pre-warmed ladder
  (:class:`~sparkdl_tpu.autotune.targets.RechunkTarget`), degrading
  PR 4's "every dispatch is ONE compiled shape" to "one of K
  pre-warmed shapes, zero cold retraces".

Arming follows the tracer/watchdog precedent: ``SPARKDL_TPU_AUTOTUNE=1``
in the environment or :meth:`AutotuneController.arm` (the override
wins); the step cadence is ``SPARKDL_TPU_AUTOTUNE_INTERVAL_S`` (default
2s; a typo degrades to the default with one warning). Disarmed,
:func:`poll` — the hook the runners and the serve dispatcher call after
each unit of work — returns after a single armed-check, the same
shared-no-op regime as the tracer (<10µs, pinned by
``tests/test_autotune.py``). There is no controller thread: steps run
on the hot-loop thread that happened to poll past the interval, so an
idle pipeline is never re-tuned on stale windows and the controller
adds no new thread that can wedge.

Observability: every step/apply lands on the ``autotune`` span lane,
decisions/oscillations/clamps count into the metrics registry,
per-knob values publish as ``autotune.knob.<target>.<knob>`` gauges,
and :meth:`AutotuneController.state` rides in every flight-recorder
bundle (docs/OBSERVABILITY.md, docs/PERFORMANCE.md).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, List, Optional

from sparkdl_tpu.obs.registry import default_registry
from sparkdl_tpu.obs.trace import span

logger = logging.getLogger(__name__)

_TRUE = ("1", "true", "yes", "on")

#: step cadence (seconds) when SPARKDL_TPU_AUTOTUNE_INTERVAL_S is unset
#: — long enough for a window to hold several dispatches, short enough
#: to track a link whose bandwidth moves between minutes
DEFAULT_INTERVAL_S = 2.0


def _env_armed() -> bool:
    return os.environ.get("SPARKDL_TPU_AUTOTUNE", "").lower() in _TRUE


# (raw env string, parsed value): read per armed step — a config typo
# must warn ONCE per value, not per step (the watchdog-threshold
# precedent)
_env_interval_cache: Optional[tuple] = None


def _env_interval() -> float:
    global _env_interval_cache
    raw = os.environ.get("SPARKDL_TPU_AUTOTUNE_INTERVAL_S", "")
    cached = _env_interval_cache
    if cached is not None and cached[0] == raw:
        return cached[1]
    try:
        v = float(raw) if raw else DEFAULT_INTERVAL_S
        if v < 0:
            raise ValueError(v)
    except ValueError:
        logger.warning(
            "SPARKDL_TPU_AUTOTUNE_INTERVAL_S=%r is not a non-negative "
            "number; using the default %.1fs", raw, DEFAULT_INTERVAL_S)
        v = DEFAULT_INTERVAL_S
    _env_interval_cache = (raw, v)
    return v


class Knob:
    """One tunable: bounds, a getter/setter pair, and the hysteresis
    state the controller keeps per knob (cooldown after a change,
    freeze after a reverted trial, last direction for oscillation
    detection). Mutated only on the controller's single-stepper (the
    step lock serializes steps), so it carries no lock of its own."""

    __slots__ = ("name", "_get", "_set", "lo", "hi", "cooldown",
                 "frozen_for", "last_dir", "steps_since_change")

    def __init__(self, name: str, get: Callable[[], Any],
                 set: Callable[[Any], None], lo, hi):
        if lo > hi:
            raise ValueError(f"knob {name!r}: lo {lo} > hi {hi}")
        self.name = name
        self._get = get
        self._set = set
        self.lo = lo
        self.hi = hi
        self.cooldown = 0
        self.frozen_for = 0
        self.last_dir = 0
        self.steps_since_change = 0

    @property
    def value(self):
        return self._get()

    def set(self, v) -> None:
        self._set(v)

    def clamp(self, v):
        return min(self.hi, max(self.lo, v))

    def usable(self) -> bool:
        """Whether the controller would currently accept a non-forced
        change (targets use this to skip proposing into a cooldown)."""
        return self.cooldown == 0 and self.frozen_for == 0

    def freeze(self, steps: int) -> None:
        """Stop accepting changes for ``steps`` controller steps — the
        explore-didn't-pay / oscillation backoff."""
        self.frozen_for = max(self.frozen_for, int(steps))

    def tick(self) -> None:
        self.cooldown = max(0, self.cooldown - 1)
        self.frozen_for = max(0, self.frozen_for - 1)
        self.steps_since_change += 1

    def describe(self) -> dict:
        return {"name": self.name, "value": self.value,
                "lo": self.lo, "hi": self.hi,
                "cooldown": self.cooldown,
                "frozen_for": self.frozen_for,
                "last_dir": self.last_dir}


class Proposal:
    """One bounded knob change a target wants: ``force`` marks trial
    reverts, which bypass cooldown and never count as oscillation (a
    revert is the trial machinery working, not the loop hunting)."""

    __slots__ = ("knob", "value", "reason", "force")

    def __init__(self, knob: Knob, value, reason: str,
                 force: bool = False):
        self.knob = knob
        self.value = value
        self.reason = reason
        self.force = force


class AutotuneController:
    """The process-wide measure→decide→apply loop (module docstring).
    One singleton (:func:`controller`) is what the hot-loop
    :func:`poll` hooks drive; standalone instances exist for tests."""

    # sparkdl-lint H3 contract: poll() can race from every hot-loop
    # thread and state() from a telemetry scrape — bookkeeping writes
    # hold self._lock (the step lock serializes the step body itself)
    _lock_guards = ("steps", "decisions_applied", "oscillations",
                    "clamps")

    #: steps a knob rests after any accepted change (hysteresis)
    cooldown_steps = 2
    #: a direction flip within this many steps of the last change is
    #: an oscillation — refused, counted, and frozen out
    osc_window = 3
    #: steps a knob stays frozen after a reverted trial / oscillation
    freeze_steps = 64
    #: initial steps that only build measurement windows (compile and
    #: cache warmup pollute the first rates — never act on them)
    warmup_steps = 2

    def __init__(self, interval_s: Optional[float] = None):
        # None → follow the env; a number → programmatic override
        self._interval_override = interval_s
        self._armed_override: Optional[bool] = None
        self._lock = threading.Lock()
        # serializes step bodies; poll() try-acquires so a hot loop
        # NEVER blocks on a step another thread is running
        self._step_lock = threading.Lock()
        self._targets: List[Any] = []
        self._last_step_t = float("-inf")
        self.steps = 0
        self.decisions_applied = 0
        self.oscillations = 0
        self.clamps = 0

    # -- arming --------------------------------------------------------------

    @property
    def armed(self) -> bool:
        ov = self._armed_override
        if ov is not None:
            return ov
        return _env_armed()

    @property
    def interval_s(self) -> float:
        if self._interval_override is not None:
            return self._interval_override
        return _env_interval()

    def arm(self, interval_s: Optional[float] = None) -> None:
        """Tune regardless of SPARKDL_TPU_AUTOTUNE; an explicit
        ``interval_s`` overrides the env cadence too (0 = decide on
        every poll — the deterministic bench/test mode)."""
        if interval_s is not None:
            if interval_s < 0:
                raise ValueError(
                    f"interval_s must be >= 0, got {interval_s}")
            self._interval_override = interval_s
        self._armed_override = True

    def disarm(self) -> None:
        """Stop tuning regardless of the env; attached targets keep
        their current knob values (the last applied config stands)."""
        self._armed_override = False

    def arm_from_env(self) -> None:
        """Drop the programmatic overrides; follow the env again."""
        self._armed_override = None
        self._interval_override = None

    def reset(self) -> None:
        """Detach every target, zero the bookkeeping, and follow the
        env again (bench/test epilogue — knob values already applied
        to runners/sessions are left as they are)."""
        with self._step_lock:
            with self._lock:
                self._targets.clear()
                self.steps = 0
                self.decisions_applied = 0
                self.oscillations = 0
                self.clamps = 0
            self._last_step_t = float("-inf")
        self.arm_from_env()

    # -- targets -------------------------------------------------------------

    def attach(self, target):
        """Register a target (ServeTarget / RechunkTarget /
        PipelineTarget — anything with ``name``, ``propose(warming)``,
        ``knobs()``, ``describe()``); returns it for chaining.

        If the controller is already armed and the target has an
        ``on_attach`` hook (RechunkTarget's ladder prewarm), it runs
        HERE, on the caller's setup thread — heavy one-time work
        (compiling every ladder rung) must not run inside a hot loop's
        first step, where it would eat a watchdog heartbeat budget."""
        if self.armed:
            prep = getattr(target, "on_attach", None)
            if prep is not None:
                prep()
        with self._lock:
            self._targets.append(target)
        return target

    def detach(self, target) -> None:
        with self._lock:
            if target in self._targets:
                self._targets.remove(target)

    def targets(self) -> List[Any]:
        with self._lock:
            return list(self._targets)

    # -- the loop ------------------------------------------------------------

    def maybe_step(self) -> None:
        """The :func:`poll` body: step iff the interval elapsed and no
        other thread is mid-step (try-lock — a hot loop never waits
        here)."""
        if time.perf_counter() - self._last_step_t < self.interval_s:
            return
        if not self._step_lock.acquire(blocking=False):
            return
        try:
            now = time.perf_counter()
            if now - self._last_step_t < self.interval_s:
                return
            self._step_locked(now)
        finally:
            self._step_lock.release()

    def step(self) -> None:
        """One deterministic measure→decide→apply round — what tests
        and the bench drive directly; production runs reach it through
        :func:`poll`."""
        with self._step_lock:
            self._step_locked(time.perf_counter())

    def _step_locked(self, now: float) -> None:
        self._last_step_t = now
        with self._lock:
            self.steps += 1
            step_no = self.steps
            targets = list(self._targets)
        if not targets:
            return
        warming = step_no <= self.warmup_steps
        with span("autotune.step", lane="autotune", step=step_no,
                  warming=warming):
            for target in targets:
                try:
                    proposals = target.propose(warming) or []
                except Exception:
                    logger.exception(
                        "autotune: target %r propose failed; skipping",
                        getattr(target, "name", target))
                    proposals = []
                for p in proposals:
                    self._apply(target, p)
                for knob in target.knobs():
                    knob.tick()

    def _apply(self, target, p: Proposal) -> bool:
        """Hysteresis + bounds around one knob write; returns whether
        the knob actually moved. Targets learn a refused trial by
        seeing the knob still at its old value next window."""
        knob = p.knob
        cur = knob.value
        if not p.force and not knob.usable():
            return False
        v = knob.clamp(p.value)
        clamped = v != p.value
        if v == cur:
            if clamped:
                # the proposal wanted past the bound and the bound is
                # where we already are — record the pressure
                self._count("clamps")
            return False
        direction = 1 if v > cur else -1
        if (not p.force and knob.last_dir
                and direction != knob.last_dir
                and knob.steps_since_change <= self.osc_window):
            # a quick direction flip is the loop hunting, not control:
            # refuse it, count it, and back the knob off hard
            self._count("oscillations")
            knob.freeze(self.freeze_steps)
            logger.warning(
                "autotune: refused oscillating change of %s.%s "
                "(%s -> %s within %d steps of the last move); knob "
                "frozen for %d steps", target.name, knob.name, cur, v,
                knob.steps_since_change, self.freeze_steps)
            return False
        with span("autotune.apply", lane="autotune",
                  target=target.name, knob=knob.name, frm=cur, to=v,
                  reason=str(p.reason)[:120]):
            knob.set(v)
        knob.last_dir = 0 if p.force else direction
        knob.cooldown = self.cooldown_steps
        knob.steps_since_change = 0
        if clamped:
            self._count("clamps")
        self._count("decisions")
        default_registry().gauge(
            f"autotune.knob.{target.name}.{knob.name}").set(float(v))
        logger.info("autotune: %s.%s %s -> %s (%s)", target.name,
                    knob.name, cur, v, p.reason)
        return True

    def _count(self, what: str) -> None:
        default_registry().counter(f"autotune.{what}").add()
        with self._lock:
            if what == "decisions":
                self.decisions_applied += 1
            elif what == "oscillations":
                self.oscillations += 1
            elif what == "clamps":
                self.clamps += 1

    # -- the scrape-able state (flight bundles, /statusz readers) ------------

    def state(self) -> dict:
        """Controller + per-target knob state for the flight
        recorder's bundles; every target describes independently — a
        broken target must not cost the postmortem."""
        with self._lock:
            targets = list(self._targets)
            out = {"armed": self.armed,
                   "interval_s": self.interval_s,
                   "steps": self.steps,
                   "warmup_steps": self.warmup_steps,
                   "decisions": self.decisions_applied,
                   "oscillations": self.oscillations,
                   "clamps": self.clamps}
        described = []
        for t in targets:
            try:
                described.append(t.describe())
            except Exception as e:
                described.append({"error": f"{type(e).__name__}: {e}"})
        out["targets"] = described
        return out

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        # locks and attached targets (live runner/session handles) are
        # process-local; arming config and lifetime counters travel
        state = self.__dict__.copy()
        del state["_lock"]
        del state["_step_lock"]
        del state["_targets"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._step_lock = threading.Lock()
        self._targets = []
        self._last_step_t = float("-inf")


_CONTROLLER = AutotuneController()


def controller() -> AutotuneController:
    """THE process-wide controller the :func:`poll` hooks drive."""
    return _CONTROLLER


def poll() -> None:
    """The hot-loop hook (runner.run epilogues, the serve dispatcher):
    disarmed it returns after one armed-check — the tracer's
    shared-no-op regime, overhead pinned alongside the span bound."""
    c = _CONTROLLER
    if not c.armed:
        return
    c.maybe_step()
