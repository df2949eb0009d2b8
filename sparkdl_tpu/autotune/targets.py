"""Autotune targets: what the controller measures and which knobs it
may move.

Each target wraps one live object (a runner, a serve session) and
turns the cumulative metrics that object already keeps into per-window
rates — no new hot-path sampling. ``propose(warming)`` returns bounded
single-step :class:`~sparkdl_tpu.autotune.core.Proposal`\\ s; the
controller owns hysteresis, clamping, and oscillation refusal.

Speculative moves (widening the decode pool, climbing the shape ladder) run
as **trials**: apply one step, evaluate the next traffic window's
throughput, keep the step only if it paid ``min_gain``, otherwise
revert and freeze the knob — so a knob that cannot help on this
host/link stops being poked instead of oscillating. Signal-shaped
moves (shrinking a saturated coalesce window, shedding read-ahead under
memory pressure, stepping the ladder down under heavy padding) apply
directly off their signal with the controller's cooldown as the only
damping.

All knob writes are single int/float attribute stores the owning hot
loop re-reads at its next unit of work — shape-safe, lock-free,
watchdog-safe (controller module docstring).
"""

from __future__ import annotations

import itertools
import logging
from typing import List, Optional

import numpy as np

from sparkdl_tpu.autotune.core import Knob, Proposal
from sparkdl_tpu.obs.registry import default_registry

logger = logging.getLogger(__name__)

_SEQ = itertools.count(1)


class _TrialMixin:
    """The explore→evaluate→revert machinery shared by targets whose
    upward moves are speculative. A trial records (knob, old value,
    baseline throughput, proposed value); the next traffic window
    either keeps the move (gain ≥ ``min_gain``) or reverts and
    freezes. A trial whose proposal the controller refused (cooldown /
    oscillation guard) is dropped without judgment — the knob never
    moved, so there is nothing to evaluate."""

    #: relative throughput gain a trial must show to be kept
    min_gain = 0.02
    #: controller steps a knob rests after a reverted trial
    freeze_steps = 64
    #: how stale a ledger window may be and still count as a prior
    #: (in multiples of the ledger's own window length) — a verdict
    #: from minutes ago describes a different pipeline
    ledger_prior_max_windows = 10.0

    _trial: Optional[tuple] = None

    def _ledger_prior(self) -> Optional[str]:
        """The live roofline's ``bound_by`` verdict as a measured
        prior (obs/ledger.py — READ-only: targets never tick or write
        the ledger). ``None`` when no fresh window exists, so
        processes that never ran the ledger tune exactly as
        before."""
        from sparkdl_tpu.obs.ledger import ledger
        led = ledger()
        return led.last_bound(
            max_age_s=self.ledger_prior_max_windows * led.window_s)

    def _start_trial(self, knob: Knob, proposed, tput: float,
                     reason: str, out: List[Proposal]) -> None:
        self._trial = (knob, knob.value, tput, proposed)
        out.append(Proposal(knob, proposed, reason))

    def _eval_trial(self, tput: float, out: List[Proposal]) -> bool:
        """Returns True when a revert was emitted (the caller should
        not explore further this window).

        EVERY completed trial freezes its knob — kept gains persist
        but the next climb waits out the freeze epoch. Without this, a
        noisy window that happens to clear ``min_gain`` re-arms the
        trial immediately and the knob random-walks toward its bound
        instead of settling; with it, convergence is structural (each
        knob completes at most one trial per epoch) and a genuinely
        faster depth still climbs one validated step per epoch."""
        if self._trial is None:
            return False
        knob, old, base, proposed = self._trial
        self._trial = None
        if knob.value == old:
            return False        # controller refused the trial
        if tput < base * (1.0 + self.min_gain):
            knob.freeze(self.freeze_steps)
            out.append(Proposal(
                knob, old,
                f"revert {knob.name}: {tput:.1f} rows/s did not beat "
                f"{base:.1f} by {self.min_gain:.0%}; frozen "
                f"{self.freeze_steps} steps", force=True))
            return True
        knob.freeze(self.freeze_steps)      # kept — settle the epoch
        return False


class PipelineTarget(_TrialMixin):
    """Tunes a :class:`~sparkdl_tpu.data.engine.LocalEngine`'s
    parallel host pipeline (``data/pipeline.py``):
    ``pipeline_workers`` (the decode worker pool) and
    ``pipeline_read_ahead`` (the ordered re-merge's look-ahead
    window).

    Deepening is **trial-gated and prior-vetoed in the raising
    direction**: the pool only helps while the DECODE lane binds, so a
    worker (then read-ahead) step up is proposed only when the live
    roofline's latest window says ``bound_by == "decode"``
    (obs/ledger.py — read-only) and is
    kept only if the next window's merged rows per pooled-stream-active
    second pays ``min_gain``;
    otherwise it reverts and the knob freezes for the epoch. With no
    fresh ledger window there is no evidence a deeper pool can pay —
    the target proposes nothing rather than exploring blind (workers
    are processes; idle ones are not free the way idle queue slots
    are).

    Shedding is signal-shaped: a ``memory_pressure`` hook (e.g. a
    host-RSS check) reclaims read-ahead
    first (each look-ahead slot parks one decoded fragment), then
    workers. Knob writes are single int attribute stores the engine
    re-reads at its next ``execute()``/submission wave — shape-safe,
    lock-free, watchdog-safe (the repo-wide apply discipline); worker
    count 1 means serial (the pool disengages entirely)."""

    def __init__(self, engine, name: Optional[str] = None,
                 max_workers: Optional[int] = None,
                 max_read_ahead: int = 16,
                 memory_pressure=None):
        import os
        self.engine = engine
        self.name = name or f"pipeline{next(_SEQ)}"
        self.memory_pressure = memory_pressure
        cap = int(max_workers if max_workers is not None
                  else max(2, os.cpu_count() or 2))
        self._workers = Knob(
            "pipeline_workers",
            get=lambda: int(engine.pipeline_workers),
            set=lambda v: setattr(engine, "pipeline_workers", int(v)),
            lo=1, hi=cap)
        self._read_ahead = Knob(
            "pipeline_read_ahead",
            get=lambda: int(engine.pipeline_read_ahead),
            set=lambda v: setattr(engine, "pipeline_read_ahead",
                                  int(v)),
            lo=1, hi=int(max_read_ahead))
        # the disaggregated decode fleet's fan-out width
        # (sparkdl_tpu/inputsvc; docs/DATA_SERVICE.md): only an engine
        # CONFIGURED with endpoints grows this knob — the ceiling is
        # the provisioned fleet size, and the apply is the same plain
        # int attribute store the engine re-reads per execute()
        fleet = len(getattr(engine, "inputsvc_endpoints", None) or ())
        self._remote: Optional[Knob] = None
        if fleet >= 1:
            self._remote = Knob(
                "inputsvc_workers",
                get=lambda: int(engine.inputsvc_workers),
                set=lambda v: setattr(engine, "inputsvc_workers",
                                      int(v)),
                lo=1, hi=fleet)
        self._prev: Optional[tuple] = None

    def knobs(self) -> List[Knob]:
        out = [self._workers, self._read_ahead]
        if self._remote is not None:
            out.append(self._remote)
        return out

    def _window(self) -> Optional[float]:
        """Merged rows per pooled-stream-ACTIVE second over the window
        since the last call — ``pipeline.rows`` over
        ``pipeline.stream_seconds``, both fed by the ordered re-merge
        (active seconds: wall-clock idle between executes must not
        deflate a trial's evaluation and spuriously revert-freeze a
        good step). None when no pooled stream finished in the
        window."""
        reg = default_registry()
        # remote decode streams (sparkdl_tpu/inputsvc) feed the same
        # merged-rows-per-active-second signal through their own
        # counters — a purely remote stream must still evaluate an
        # inputsvc_workers trial
        rows = (reg.counter("pipeline.rows").value
                + reg.counter("inputsvc.rows").value)
        active = (reg.counter("pipeline.stream_seconds").value
                  + reg.counter("inputsvc.stream_seconds").value)
        prev, self._prev = self._prev, (rows, active)
        if prev is None:
            return None
        drows = rows - prev[0]
        dsec = active - prev[1]
        if drows <= 0 or dsec <= 0:
            return None
        return drows / dsec

    def propose(self, warming: bool) -> List[Proposal]:
        tput = self._window()
        out: List[Proposal] = []
        if tput is None or warming:
            return out
        if self._eval_trial(tput, out):
            return out
        if self.memory_pressure is not None and self.memory_pressure():
            # reclaim look-ahead fragments first, then whole workers
            if self._read_ahead.value > self._read_ahead.lo:
                out.append(Proposal(self._read_ahead,
                                    self._read_ahead.value - 1,
                                    "memory pressure"))
            elif self._workers.value > self._workers.lo:
                out.append(Proposal(self._workers,
                                    self._workers.value - 1,
                                    "memory pressure"))
            return out
        if self._ledger_prior() != "decode":
            # the decode lane is not the wall right now: a deeper host
            # pool cannot move the pipeline, and the trial would burn
            # a freeze epoch learning that
            return out
        reason = "ledger prior: decode lane binds; deepen host pipeline"
        if (self._remote is not None and self._remote.usable()
                and self._remote.value < self._remote.hi):
            # widen the PROVISIONED remote fleet before growing local
            # pool processes: remote lanes are capacity that already
            # exists (the trial still validates the step pays)
            self._start_trial(
                self._remote, self._remote.value + 1, tput,
                "ledger prior: decode lane binds; widen the remote "
                "decode fleet", out)
        elif self._workers.usable() \
                and self._workers.value < self._workers.hi:
            self._start_trial(self._workers, self._workers.value + 1,
                              tput, reason, out)
        elif self._read_ahead.usable() \
                and self._read_ahead.value < self._read_ahead.hi:
            self._start_trial(self._read_ahead,
                              self._read_ahead.value + 1, tput,
                              reason + " (read-ahead)", out)
        return out

    def describe(self) -> dict:
        return {"name": self.name, "kind": "pipeline",
                "trial_open": self._trial is not None,
                "ledger_prior": self._ledger_prior(),
                "knobs": [k.describe() for k in self.knobs()]}


class ServeTarget:
    """Tunes one serve session's dynamic micro-batching window
    (``ModelSession.max_wait_s``): shrink it when the queue saturates
    batches without waiting (the window only adds latency then), grow
    it when fill is poor and the p99 budget has headroom (waiting
    longer is exactly how coalescing buys fill). The deadband between
    ``lo_fill`` and ``hi_fill`` plus the controller cooldown is the
    hysteresis — load that sits in the band moves nothing."""

    #: window fill below which the coalesce window grows
    lo_fill = 0.6
    #: window fill above which the coalesce window shrinks
    hi_fill = 0.95
    #: multiplicative step (bounded: one notch per decision)
    grow_factor = 1.5

    def __init__(self, session, name: Optional[str] = None,
                 min_wait_s: float = 0.0,
                 max_wait_cap_s: Optional[float] = None,
                 latency_budget_s: Optional[float] = None):
        self.session = session
        self.name = name or f"serve:{session.name}"
        if max_wait_cap_s is None:
            max_wait_cap_s = max(4.0 * session.max_wait_s, 0.02)
        if latency_budget_s is None:
            latency_budget_s = session.config.default_deadline_s
        self.latency_budget_s = latency_budget_s
        self._wait = Knob(
            "max_wait_s",
            get=lambda: session.max_wait_s,
            set=lambda v: setattr(session, "max_wait_s", float(v)),
            lo=float(min_wait_s), hi=float(max_wait_cap_s))
        self._prev: Optional[tuple] = None

    def knobs(self) -> List[Knob]:
        return [self._wait]

    def propose(self, warming: bool) -> List[Proposal]:
        m = self.session.metrics
        cur_counts = (m.batches, m.batch_rows, m.batch_capacity_rows)
        prev, self._prev = self._prev, cur_counts
        if prev is None or warming:
            return []
        dbatches = cur_counts[0] - prev[0]
        dcap = cur_counts[2] - prev[2]
        if dbatches <= 0 or dcap <= 0:
            return []
        fill = (cur_counts[1] - prev[1]) / dcap
        cur = self._wait.value
        if fill >= self.hi_fill and cur > self._wait.lo:
            # saturated: arrivals outrun dispatch — the window is pure
            # added latency now
            return [Proposal(self._wait, max(self._wait.lo, cur / 2.0),
                             f"fill {fill:.0%} saturated; shrink the "
                             "coalesce window")]
        if fill < self.lo_fill and cur < self._wait.hi:
            new = min(self._wait.hi,
                      max(cur * self.grow_factor, 0.001))
            if self.latency_budget_s is not None:
                p99 = m.latency_seconds(0.99)
                if p99 + (new - cur) > 0.5 * self.latency_budget_s:
                    return []   # no p99 headroom to spend on fill
            return [Proposal(self._wait, new,
                             f"fill {fill:.0%}; grow the coalesce "
                             "window for fill")]
        return []

    def describe(self) -> dict:
        return {"name": self.name, "kind": "serve",
                "model": self.session.name,
                "latency_budget_s": self.latency_budget_s,
                "knobs": [k.describe() for k in self.knobs()]}


class RechunkTarget(_TrialMixin):
    """Moves a :class:`~sparkdl_tpu.runtime.runner.BatchRunner`'s
    device batch — and with it the engine's re-chunk hint, which
    follows ``preferred_chunk`` live through
    :class:`~sparkdl_tpu.data.frame.LiveBatchHint` — along a small
    pre-warmed shape **ladder**.

    The ladder is the retrace guarantee: :meth:`prewarm` traces and
    compiles every rung up front (one zeros run each through the jit
    cache), so PR 4's "every dispatch is ONE compiled shape" degrades
    to "one of K pre-warmed shapes, **zero cold retraces**" — the
    sparkdl-lint H2 discipline kept at runtime. Decisions only ever
    move one rung and only among warmed rungs.

    Down moves are signal-shaped: a window whose mean dispatched fill
    (rows / batches·chunk) sits under ``down_fill`` is paying the
    small-partition padding tax — a smaller rung strictly reduces pad.
    Up moves (amortizing per-dispatch latency on high-RTT links) are
    speculative and trial-gated.

    NOT for runners registered behind a ``ModelServer`` — a serve
    session fixes its chunk at registration (``session.chunk``) and
    its warmup covers exactly that one shape."""

    #: window mean batch fill below which the ladder steps down
    down_fill = 0.5
    #: window mean batch fill above which an up-trial may start
    up_fill = 0.98

    def __init__(self, runner, ladder=None, name: Optional[str] = None):
        self.runner = runner
        self.name = name or f"rechunk{next(_SEQ)}"
        base = int(runner.batch_size)
        if ladder is None:
            ladder = {max(1, base // 2), base, base * 2}
        self.ladder = sorted({int(r) for r in ladder})
        if any(r <= 0 for r in self.ladder):
            raise ValueError(f"ladder rungs must be positive, got "
                             f"{self.ladder}")
        if base not in self.ladder:
            raise ValueError(
                f"runner batch_size {base} must be one of the ladder "
                f"rungs {self.ladder} (the current shape is warmed by "
                "construction)")
        self.warmed = False
        self._rung = Knob(
            "ladder_rung",
            get=self._current_rung,
            set=self._apply_rung,
            lo=0, hi=len(self.ladder) - 1)
        self._prev: Optional[tuple] = None

    def _current_rung(self) -> int:
        try:
            return self.ladder.index(int(self.runner.batch_size))
        except ValueError:
            return -1           # moved off-ladder externally

    def _apply_rung(self, idx) -> None:
        self.runner.batch_size = self.ladder[int(idx)]

    def knobs(self) -> List[Knob]:
        return [self._rung]

    def prewarm(self) -> int:
        """Trace + compile every rung's shape into the runner's jit
        cache — DIRECTLY through ``model_fn.jitted()`` (the exact
        callable ``_run_device`` dispatches), never by cycling the
        live ``batch_size``: a concurrent ``run()`` on another thread
        must never observe a transient rung (runner.run snapshots
        batch_size per call, but the snapshot of a mid-prewarm value
        would be a cold shape). Host backends and unknown-dim
        signatures no-op, the ``warmup_runner`` discipline.
        Idempotent; returns the number of rungs actually warmed.
        Runs at ``controller().attach`` time on the setup thread (the
        ``on_attach`` hook) when the controller is already armed; the
        lazy fallback in :meth:`propose` covers targets attached
        before arming — that path pays the compile inside a controller
        step, so prefer arm-then-attach for latency-sensitive
        processes."""
        if self.warmed:
            return 0
        mf = self.runner.model_fn
        sig = mf.input_signature
        if (getattr(mf, "backend", None) != "jax"
                or any(d is None
                       for shape, _ in sig.values() for d in shape)):
            self.warmed = True
            return 0            # nothing jitted to warm
        fn = mf.jitted()
        params = mf.device_params()
        for rung in self.ladder:
            zeros = {k: np.zeros((rung,) + tuple(shape), dtype)
                     for k, (shape, dtype) in sig.items()}
            fn(params, zeros)
        self.warmed = True
        # every rung is compiled — mark the model's programs STEADY in
        # the compile log (obs/compile_log.py): the one-of-K-prewarmed
        # guarantee becomes a runtime invariant, and any OFF-ladder
        # shape from here on counts compile.unexpected_retraces with a
        # diff naming the argument that moved
        from sparkdl_tpu.obs.compile_log import compile_log
        compile_log().mark_model_steady(mf, reason="prewarm")
        logger.info("autotune: %s pre-warmed %d ladder rungs %s",
                    self.name, len(self.ladder), self.ladder)
        return len(self.ladder)

    # controller().attach runs this on the setup thread when armed —
    # the ladder compile must not land inside a hot loop's first step
    on_attach = prewarm

    def propose(self, warming: bool) -> List[Proposal]:
        m = self.runner.metrics
        if not warming and not self.warmed:
            # prewarm FIRST, then baseline the window after it — the
            # ladder's zeros runs must not read as traffic
            self.prewarm()
            self._prev = (m.rows, m.batches, m.seconds)
            return []
        cur_counts = (m.rows, m.batches, m.seconds)
        prev, self._prev = self._prev, cur_counts
        if warming or prev is None:
            return []
        drows = cur_counts[0] - prev[0]
        dbatches = cur_counts[1] - prev[1]
        dsec = cur_counts[2] - prev[2]
        if drows <= 0 or dbatches <= 0 or dsec <= 0:
            return []
        out: List[Proposal] = []
        tput = drows / dsec
        if self._eval_trial(tput, out):
            return out
        idx = self._rung.value
        if idx < 0:
            return []           # batch_size moved off-ladder externally
        fill = drows / (dbatches * self.runner.batch_size)
        if fill < self.down_fill and idx > self._rung.lo:
            out.append(Proposal(
                self._rung, idx - 1,
                f"batch fill {fill:.0%}: padding tax — step the shape "
                f"ladder down to {self.ladder[idx - 1]}"))
        elif (fill >= self.up_fill and idx < self._rung.hi
                and self._rung.usable()):
            self._start_trial(
                self._rung, idx + 1, tput,
                f"batch fill {fill:.0%}: amortize per-dispatch "
                f"latency — trial rung {self.ladder[idx + 1]}", out)
        return out

    def describe(self) -> dict:
        return {"name": self.name, "kind": "rechunk",
                "ladder": list(self.ladder),
                "batch_size": int(self.runner.batch_size),
                "prewarmed": self.warmed,
                "trial_open": self._trial is not None,
                "knobs": [k.describe() for k in self.knobs()]}


class FleetTarget(_TrialMixin):
    """Grows a logical model's replica count through the fleet
    registry when the live roofline says SERVING is the binding
    ceiling and the replicas' request queues stay deep.

    The knob is ``ModelRegistry.scale`` — grow-only (scale never tears
    down a live session mid-traffic), so there is no trial/revert
    machinery here: a replica is added only behind TWO measured gates
    and the knob's own cooldown, never speculatively:

    * the ledger's ``bound_by`` verdict must be the **serve** lane
      (``_TrialMixin._ledger_prior()``; obs/ledger.py) — compute- or
      decode-bound pipelines gain nothing from more serve sessions,
      and a process that never ran the ledger never scales (no prior,
      no growth: the expensive knob needs positive evidence);
    * the mean queue depth per replica must exceed
      ``grow_depth_batches`` dispatch batches — momentary bursts the
      coalesce window absorbs do not count.

    Growth is cheap precisely because of the warm-start cache: the new
    replica deserializes the persisted AOT executable instead of
    compiling (fleet/warmstart.py), which is why this knob is safe to
    hand to the controller at all.
    """

    #: mean per-replica queue depth (in dispatch batches) that reads
    #: as "persistently behind" — below it the fleet never grows
    grow_depth_batches = 2.0

    def __init__(self, registry, model: str,
                 name: Optional[str] = None,
                 max_replicas: int = 4):
        self.registry = registry
        self.model = model
        self.name = name or f"fleet:{model}"
        entry = registry.entry(model)     # typed KeyError surface
        self._replicas = Knob(
            "replicas",
            get=lambda: len(registry.entry(model).replicas),
            set=lambda v: registry.scale(model, int(v)),
            lo=len(entry.replicas), hi=int(max_replicas))

    def knobs(self) -> List[Knob]:
        return [self._replicas]

    def _mean_depth(self) -> Optional[float]:
        """Mean request-queue depth across the model's live replicas
        (``ModelSession.queue_depth()``), ``None`` when unreadable."""
        try:
            entry = self.registry.entry(self.model)
            server = self.registry._server
            depths = [server.session(r).queue_depth()
                      for r in entry.replicas]
        # sparkdl-lint: allow[H12] -- measurement probe: a replica mid-teardown means "no signal this window", not a controller crash
        except Exception:
            return None
        return (sum(depths) / len(depths)) if depths else None

    def propose(self, warming: bool) -> List[Proposal]:
        if warming or not self._replicas.usable():
            return []
        cur = self._replicas.value
        if cur >= self._replicas.hi:
            return []
        if self._ledger_prior() != "serve":
            return []           # the ceiling is elsewhere — hold
        depth = self._mean_depth()
        batch = self.registry.entry(self.model).batch_size
        if depth is None or depth < self.grow_depth_batches * batch:
            return []
        return [Proposal(
            self._replicas, cur + 1,
            f"serve-bound with mean queue depth {depth:.0f} rows "
            f"(≥ {self.grow_depth_batches:g} batches of {batch}) — "
            f"grow {self.model!r} to {cur + 1} replicas")]

    def describe(self) -> dict:
        return {"name": self.name, "kind": "fleet",
                "model": self.model,
                "ledger_prior": self._ledger_prior(),
                "knobs": [k.describe() for k in self.knobs()]}
