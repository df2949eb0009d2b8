"""ModelServer: the in-process online inference front-end.

Every entry point before this layer was offline — one caller hands a
full materialized batch to ``BatchRunner.run`` / ``ShardedBatchRunner
.run`` and blocks. Online traffic is the opposite shape: many small
concurrent requests, each wanting an answer soon. The server sits
between the two (docs/SERVING.md):

* :meth:`ModelServer.submit` is the thread-safe front door: validate
  against the model signature, admit against the bounded row queue
  (or reject with the typed ``ServerOverloaded`` — backpressure, never
  unbounded growth), return a ``concurrent.futures.Future``.
* one dispatcher thread per registered model session drains the queue
  into ``preferred_chunk``-aligned micro-batches (serve/batching.py)
  and runs them through the session's runner — so every device
  dispatch is full-shaped, the jit cache sees ONE shape forever, and
  the existing zero-copy ship path does the actual work. A coalesced
  multi-request batch stages through the session's persistent
  :class:`PadStaging` buffers (``stage_parts``); a single
  full-chunk request passes through as plain views — zero copies.
* mesh-backed sessions dispatch through ``ShardedBatchRunner.run``,
  which already takes ``collective_launch()`` for model-parallel
  programs — the serve layer inherits the launch-ordering discipline
  rather than re-implementing it (``ModelSession.collective`` exposes
  the ``mesh_has_collectives`` policy for observability).
* :meth:`ModelServer.warmup` pre-traces every session's jitted
  program at its device batch shape, so the first user request never
  pays the compile.
* :meth:`ModelServer.close` follows the engine quiesce discipline:
  graceful drain by default (finish the admitted queue, bounded by
  ``drain_timeout_s``, warn — never hang), or fail-fast with the
  typed ``ServerClosed`` when ``drain=False``.

Observability rides the ``serve`` obs lane (``enqueue`` / ``coalesce``
/ ``dispatch`` / ``warmup`` spans) plus ``serve.*`` registry metrics
(docs/OBSERVABILITY.md): live queue depth gauges set on the hot path,
cumulative counters published from :class:`ServeMetrics` after every
dispatch/rejection. Armed (SPARKDL_TPU_TRACE / SPARKDL_TPU_REQUEST_LOG
— obs/request_log.py), every submit additionally mints a request_id
and records a per-request phase timeline (queue → coalesce → staging →
device → reassembly) whose worst cases become latency-reservoir
exemplars; request outcomes always feed the SLO tracker's separate
availability stream (obs/slo.py) — successes carry their latency,
deadline misses / dispatch failures / rejections / abandons count
against availability and NEVER pollute the latency percentiles.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional

import numpy as np

from sparkdl_tpu.autotune.core import poll as autotune_poll
from sparkdl_tpu.obs import default_registry, span
from sparkdl_tpu.obs import flight
from sparkdl_tpu.obs.ledger import ledger_poll
from sparkdl_tpu.obs.request_log import request_log
from sparkdl_tpu.obs.slo import slo_tracker
from sparkdl_tpu.obs.watchdog import watch as watchdog_watch
from sparkdl_tpu.parallel.inference import ShardedBatchRunner
from sparkdl_tpu.parallel.mesh import mesh_has_collectives
from sparkdl_tpu.resilience.errors import is_transient
from sparkdl_tpu.resilience.faults import maybe_fail
from sparkdl_tpu.resilience.policy import (
    CircuitBreaker,
    CircuitOpen,
    RetryPolicy,
)
from sparkdl_tpu.runtime.runner import (
    BatchRunner,
    ChunkPhases,
    PadStaging,
    check_against_signature,
    check_row_counts,
)
from sparkdl_tpu.serve.batching import (
    DeadlineExceeded,
    MicroBatch,
    Request,
    RequestQueue,
    ServerClosed,
    ServerOverloaded,
    ShedForPriority,
)
from sparkdl_tpu.serve.config import ServeConfig
from sparkdl_tpu.serve.metrics import ServeMetrics

logger = logging.getLogger(__name__)


class ModelSession:
    """One registered model behind the server: its runner, its bounded
    queue, its dispatcher thread, its persistent coalesce staging.

    Created via :meth:`ModelServer.register`; the dispatcher starts
    lazily on first submit (so a pickled/shipped server needs no
    explicit restart). All result reassembly happens on the single
    dispatcher thread — the queue's condition is the only lock between
    submitters and the dispatcher, and it is never held across a
    dispatch."""

    def __init__(self, name: str, runner, config: ServeConfig,
                 metrics: ServeMetrics):
        self.name = name
        self.runner = runner
        self.config = config
        self.metrics = metrics
        self.chunk = int(runner.preferred_chunk)
        # the LIVE coalesce window, initialized from the frozen config:
        # the dispatcher re-reads it per collect, so the autotune
        # controller (sparkdl_tpu/autotune, ServeTarget) can shrink it
        # when fill saturates / grow it when p99 headroom exists — a
        # single float store between batches, never mid-collect
        self.max_wait_s = float(config.max_wait_s)
        # warmup state for /statusz + flight bundles: None = never
        # attempted, True/False = runner.warmup()'s last answer (False
        # means "nothing to warm", e.g. a host backend)
        self.warmed: Optional[bool] = None
        # resilience (docs/RESILIENCE.md): the micro-batch re-dispatch
        # policy — bounded attempts, deterministic-jitter backoff, a
        # retry budget so a broken model can't see its load amplified
        # by its own dispatcher — and the per-session circuit breaker
        # that sheds submissions fast-and-typed once the model fails
        # persistently
        self.retry_policy = RetryPolicy(
            attempts=1 + config.dispatch_retries,
            base_backoff_s=config.retry_base_backoff_s,
            max_backoff_s=max(config.retry_base_backoff_s * 8, 0.25),
            budget_ratio=config.retry_budget_ratio)
        self.circuit = CircuitBreaker(
            failure_threshold=config.circuit_failure_threshold,
            reset_timeout_s=config.circuit_reset_s,
            half_open_probes=config.circuit_probes)
        self._queue = RequestQueue()
        self._staging = PadStaging()
        self._worker: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # the fleet hot-swap's serialization point (fleet/registry.py):
        # the dispatcher holds it across each runner call, the registry
        # holds it for the params pointer flip — so a swap lands
        # BETWEEN dispatches, never inside one. Uncontended cost is one
        # lock acquire per micro-batch, not per row.
        self._swap_gate = threading.Lock()

    # -- introspection -------------------------------------------------------

    def queue_depth(self) -> int:
        """Requests waiting in this session's bounded queue right now
        — the fleet router's least-depth routing signal
        (fleet/router.py). One condition-guarded read; safe from any
        thread."""
        return self._queue.depth()

    @property
    def collective(self) -> bool:
        """Whether this session's dispatches carry cross-device
        collectives and therefore serialize under the process-wide
        launch lock inside ``ShardedBatchRunner.run`` (the
        ``mesh_has_collectives`` policy, parallel/mesh.py)."""
        return mesh_has_collectives(getattr(self.runner, "mesh", None))

    # -- submission (any thread) ---------------------------------------------

    def submit(self, inputs: Dict[str, np.ndarray],
               deadline: Optional[float] = None,
               priority: int = 0) -> Future:
        """Validate, admit, enqueue; returns the Future the dispatcher
        will resolve. Raises ``ServerOverloaded`` (queue full, or this
        request was shed for its priority class), ``CircuitOpen`` (the
        session's breaker is shedding a persistently broken model),
        ``ServerClosed``, or ``ValueError`` (signature mismatch) —
        all BEFORE enqueue, so a rejected caller holds nothing.

        ``priority`` is the SLO admission class (higher = more
        important, default 0): under saturation the queue sheds
        lowest-priority-first — a higher-priority arrival displaces
        queued lower-priority requests instead of being flat-rejected,
        and while the availability error budget is burning, arrivals
        below the highest queued class shed at admission
        (docs/RESILIENCE.md).

        Buffer ownership: the queued request BORROWS the caller's
        arrays until its future resolves (copying at admission would
        re-pay exactly the ship-side byte tax the zero-copy fast path
        exists to avoid) — a caller that reuses an input buffer must
        wait for the future first, or pass a copy. A dtype-mismatched
        input is cast (copied) at admission and is safe to reuse."""
        mf = self.runner.model_fn
        sig = mf.input_signature
        if int(priority) < 0:
            raise ValueError(
                f"priority must be >= 0, got {priority}")
        raw = {k: np.asarray(v) for k, v in inputs.items()}
        n = check_row_counts(raw)
        if n == 0:
            # zero-row submissions resolve immediately — they must not
            # occupy a batch slot or wait out the window. The runner's
            # own N=0 path supplies the schema-correct empties
            # (empty_jax_outputs for jax backends, the probe batch for
            # host models). The close() contract still applies
            # (nothing resolves after shutdown), and all declared
            # inputs must be present — only the per-row shape check is
            # moot at N=0 (empty variable-list columns arrive flat,
            # the runner contract).
            if self._queue.closing:
                raise ServerClosed("server is closed to new requests")
            missing = [k for k in sig if k not in raw]
            if missing:
                raise ValueError(
                    f"model {mf.name!r} inputs {missing} missing "
                    f"from request inputs {sorted(raw)}")
            if not self.circuit.allow():
                # the inline fast path sheds like the queued path: an
                # open breaker means this runner is failing
                # persistently — fail fast and typed
                self._reject_circuit_open(None)
            fut: Future = Future()
            t0 = time.perf_counter()
            try:
                out = self.runner.run(raw)
            except Exception:
                # the inline fast path is still a request outcome: a
                # broken runner hammered with empty probes must show
                # up as failures + availability burn, not zero-metric
                # silence ("outcomes always feed the SLO tracker") —
                # and as circuit evidence
                self.circuit.record_failure()
                self.metrics.add_request(0)
                self.metrics.add_failure()
                slo_tracker().record(ok=False)
                self.metrics.publish(default_registry())
                raise
            self.circuit.record_success()
            fut.set_result(out)
            self.metrics.add_request(0)
            slo_tracker().record(
                latency_s=time.perf_counter() - t0, ok=True)
            self.metrics.publish(default_registry())
            return fut
        check_against_signature(raw, mf)
        # cast to the signature dtype at admission (no copy when it
        # already matches): every staged/coalesced batch then has ONE
        # dtype, so the warmed jit cache is never invalidated by a
        # caller handing in float64
        cast = {k: np.asarray(raw[k], np.dtype(dtype))
                for k, (_shape, dtype) in sig.items()}

        # per-request observability (obs/request_log.py): armed runs
        # mint a request_id + phase timeline HERE — admission is where
        # the request's story starts, rejections included. Disarmed
        # this is one armed-check returning None (the shared no-op
        # regime, overhead-pinned in tests/test_request_obs.py).
        rlog = request_log()
        tl = rlog.timeline(self.name, n, time.perf_counter())

        if deadline is None:
            deadline = self.config.default_deadline_s
        abs_deadline = None
        if deadline is not None:
            if deadline <= 0:
                # deadline-aware admission: a request that is already
                # dead is failed up front, not queued — an
                # AVAILABILITY event (obs/slo.py), never a latency
                # sample
                self.metrics.add_request(n)
                self.metrics.add_deadline_miss()
                slo_tracker().record(ok=False)
                if tl is not None:
                    # flow=False: no enqueue span ever opened this
                    # request's flow — an end with no start dangles
                    rlog.record(tl.finish(time.perf_counter(),
                                          "deadline_exceeded"),
                                submitted=tl.submitted, flow=False)
                fut = Future()
                fut.set_exception(DeadlineExceeded(
                    f"deadline {deadline}s is not in the future"))
                self.metrics.publish(default_registry())
                return fut
            abs_deadline = time.perf_counter() + deadline

        reg = default_registry()
        if n > self.config.max_queue_rows:
            self.metrics.add_rejection()
            slo_tracker().record(ok=False)
            if tl is not None:
                # flow=False: rejected before the enqueue span — no
                # flow start exists to end
                rlog.record(tl.finish(time.perf_counter(), "rejected"),
                            submitted=tl.submitted, flow=False)
            self.metrics.publish(reg)
            raise ServerOverloaded(
                f"request of {n} rows can never be admitted: "
                f"max_queue_rows={self.config.max_queue_rows}")
        if not self.circuit.allow():
            # fast-and-typed shed: a persistently broken model must
            # not queue new requests toward their deadline
            # (docs/RESILIENCE.md; closed→open→half-open transitions
            # live in resilience/policy.py)
            self._reject_circuit_open(tl)
        req = Request(cast, n, abs_deadline, timeline=tl,
                      priority=int(priority))
        enq_attrs = {"rows": n, "model": self.name}
        if tl is not None:
            # visible arg + the Perfetto flow START: the dispatch
            # span(s) carrying this request step the flow, the request
            # span ends it — a split request renders as one connected
            # flow (obs/trace.py trace_events)
            enq_attrs.update(request_id=tl.rid, flow_id=tl.rid,
                             flow_ph="s")
        # SLO-aware admission (docs/RESILIENCE.md): the queue sheds
        # lowest-priority-first under saturation, and early while the
        # availability budget is burning. The burn rate is read from
        # the live slo.* gauge (published rate-limited by the serve
        # loop, refreshed at scrape time) — status() scans the whole
        # outcome window and must not run per submit.
        burn = reg.gauge("slo.availability.burn_rate").value
        watermark = int(self.config.max_queue_rows
                        * self.config.shed_watermark_frac)
        try:
            with span("enqueue", lane="serve", **enq_attrs):
                depth, victims = self._queue.offer(
                    req, self.config.max_queue_rows,
                    burn_rate=burn, watermark_rows=watermark)
        except ServerOverloaded as e:
            self.metrics.add_rejection()
            if isinstance(e, ShedForPriority):
                self.metrics.add_shed(n)
            slo_tracker().record(ok=False)
            if tl is not None:
                rlog.record(tl.finish(time.perf_counter(), "rejected"),
                            submitted=tl.submitted)
            self.metrics.publish(reg)
            raise
        for v in victims:
            # displaced for this higher-priority admission: shed
            # typed, counted, and recorded as an availability event
            # (never a latency sample)
            if v.fail(ServerOverloaded(
                    f"shed from the queue (priority {v.priority}) to "
                    f"admit a priority-{req.priority} request under "
                    f"saturation (model {self.name!r}) — retry with "
                    "bounded backoff (resilience.RetryPolicy, "
                    "docs/RESILIENCE.md) or raise priority=")):
                self.metrics.add_shed(v.n)
                slo_tracker().record(ok=False)
                self._record_outcome(v, "shed")
        if victims:
            self.metrics.publish(reg)
        # AFTER a successful admission: a submit that can only be
        # rejected (closed/overloaded) must not churn a fresh
        # short-lived dispatcher thread per call. The queued request
        # is not orphaned by the ordering — a close() racing into the
        # gap either fails it from the abandoned list (drain=False) or
        # leaves it queued for the worker started here, which drains
        # it and exits on the closed empty queue (its future resolves;
        # only a submit that RACED close can resolve after close
        # returns, and a racing submit has no ordering claim).
        self._ensure_worker()
        self.metrics.add_request(n)
        reg.gauge("serve.queue_rows").set(depth)
        reg.gauge("serve.queue_rows_peak").set_max(depth)
        return req.future

    # -- warmup --------------------------------------------------------------

    def warmup(self) -> bool:
        """Pre-trace/compile at the device batch shape so the first
        submitted request never pays the jit (runner.warmup: one zeros
        run of ``preferred_chunk`` rows — the only shape the server
        ever dispatches)."""
        with span("warmup", lane="serve", model=self.name,
                  rows=self.chunk):
            self.warmed = self.runner.warmup()
        return self.warmed

    # -- the dispatcher thread -----------------------------------------------

    def _ensure_worker(self) -> None:
        with self._lock:
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._serve_loop,
                    name=f"sparkdl-serve-{self.name}", daemon=True)
                self._worker.start()

    def _serve_loop(self) -> None:
        reg = default_registry()
        # the watchdog activity window opens AFTER the idle wait in
        # collect(): a dispatcher blocked waiting for traffic is idle,
        # not stalled — only a collected batch that never resolves
        # (the wedged-collective signature) may trip the stall verdict
        wd_source = f"serve.dispatcher:{self.name}"
        while True:
            batch = self._queue.collect(self.chunk, self.max_wait_s)
            if batch is None:
                return          # closed and drained
            # the utilization ledger's serve-lane feed (obs/ledger.py):
            # the coalesce window's wait — latency deliberately traded
            # for batch fill, clocked by collect() from first pop
            reg.counter("serve.coalesce_wait_seconds").add(
                batch.waited_s)
            with watchdog_watch(wd_source):
                for req in batch.expired:
                    # failed BEFORE dispatch: no device time for the
                    # dead — and an AVAILABILITY event, never a
                    # latency sample (the SLO populations stay
                    # separate, pinned by test)
                    if req.fail(DeadlineExceeded(
                            f"deadline passed after {time.perf_counter() - req.submitted:.3f}s queued "
                            f"(model {self.name!r})")):
                        self.metrics.add_deadline_miss()
                        slo_tracker().record(ok=False)
                        self._record_outcome(req, "deadline_exceeded")
                reg.gauge("serve.queue_rows").set(self._queue.depth())
                if batch.parts:
                    try:
                        self._dispatch(batch)
                    # sparkdl-lint: allow[H13] -- not a retry: the failed batch is failed right here (typed + accounted), never re-attempted by this loop; re-dispatch lives in _dispatch under the bounded, backed-off RetryPolicy, and this loop only continues to NEW work, paced by collect()'s blocking wait and exited via its None signal
                    except Exception as e:
                        # a failed dispatch fails ITS requests; the
                        # dispatcher keeps serving the rest of the queue
                        logger.exception(
                            "serve dispatch failed for model %r",
                            self.name)
                        # armed flight recorder: this is the unhandled-
                        # failure trigger — the bundle carries the queue
                        # state + spans that led here
                        flight.record_failure(
                            e, where=f"serve.dispatch:{self.name}")
                        for req, _lo, _rows in batch.parts:
                            if req.fail(e):
                                self.metrics.add_failure()
                                slo_tracker().record(ok=False)
                                self._record_outcome(req, "failed")
                self.metrics.publish(reg)
                # the breaker's state as a gauge (0 closed / 1 open /
                # 2 half-open; last-writer-wins across sessions, the
                # ship.inflight precedent — per-model state lives in
                # /statusz and flight bundles)
                reg.gauge("serve.circuit_state").set(
                    self.circuit.state_code)
                # error budgets ride the serve-gauge cadence, rate-
                # limited: status() scans the whole outcome window,
                # which a per-micro-batch loop must not pay per batch
                # (readers never see the throttle — /statusz computes
                # live, /metricsz re-publishes at scrape time)
                slo_tracker().publish_due(reg)
            # autotune apply point, OUTSIDE the watchdog activity
            # window: a controller step must never eat this source's
            # heartbeat budget (disarmed: one armed-check — the
            # shared-no-op regime); the ledger poll rides the same
            # cadence under the same contract
            autotune_poll()
            ledger_poll()

    def _record_outcome(self, req: Request, status: str) -> None:
        """Close out a failed/expired/abandoned request's timeline
        into the request log (no-op for disarmed requests)."""
        tl = req.timeline
        if tl is not None:
            request_log().record(
                tl.finish(time.perf_counter(), status),
                submitted=tl.submitted)

    def _reject_circuit_open(self, tl) -> None:
        """Shed one submission against the open breaker: typed,
        counted, an availability event — and cheap, which is the whole
        point (no queueing toward a dead model)."""
        self.metrics.add_circuit_rejection()
        slo_tracker().record(ok=False)
        if tl is not None:
            # flow=False: never enqueued — no flow start exists to end
            request_log().record(
                tl.finish(time.perf_counter(), "circuit_open"),
                submitted=tl.submitted, flow=False)
        self.metrics.publish(default_registry())
        st = self.circuit.status()
        raise CircuitOpen(
            f"model {self.name!r} circuit is {st['state']} after "
            f"{st['consecutive_failures']} consecutive dispatch "
            f"failures — shedding fast instead of burning your "
            f"deadline; probes resume within "
            f"{st['reset_timeout_s']}s (docs/RESILIENCE.md)")

    def _dispatch(self, batch: MicroBatch) -> None:
        """Run one collected micro-batch, re-dispatching on transient
        failure (docs/RESILIENCE.md): a failed dispatch fails only the
        requests that cannot survive a retry — everything whose
        deadline still covers the backed-off re-attempt re-dispatches
        as a smaller batch instead of the whole coalesced batch
        failing. Attempts/backoff/budget come from the session
        RetryPolicy; every outcome feeds the circuit breaker. The
        autotune poll stays OUTSIDE this loop (in _serve_loop) — a
        controller step must never ride a retry storm."""
        parts = batch.parts
        self.retry_policy.deposit()
        attempt = 0
        while True:
            try:
                self._dispatch_once(parts)
                self.circuit.record_success()
                return
            except Exception as exc:
                self.circuit.record_failure()
                attempt += 1
                # grant() raises RetryBudgetExhausted (typed, chained)
                # when only the budget refuses; None = don't retry
                # (permanent error, attempts exhausted)
                delay = self.retry_policy.grant(
                    attempt, exc, key=f"serve:{self.name}")
                if delay is None:
                    raise
                horizon = time.perf_counter() + delay
                survivors: List = []
                for part in parts:
                    req = part[0]
                    if req.deadline is None or req.deadline > horizon:
                        survivors.append(part)
                    elif req.fail(exc):
                        # no deadline budget left for the re-attempt:
                        # this request's dispatch failure is final —
                        # counted and recorded now, not after a retry
                        # it cannot use
                        self.metrics.add_failure()
                        slo_tracker().record(ok=False)
                        self._record_outcome(req, "failed")
                if not survivors:
                    raise
                self.metrics.add_retry()
                logger.warning(
                    "serve dispatch for model %r failed (%s); "
                    "re-dispatching %d/%d surviving requests in "
                    "%.3fs (attempt %d/%d)",
                    self.name, exc, len(survivors), len(parts),
                    delay, attempt, self.retry_policy.attempts)
                with span("retry_backoff", lane="serve",
                          model=self.name, attempt=attempt,
                          requests=len(survivors)):
                    time.sleep(delay)
                parts = survivors

    def _dispatch_once(self, parts: List) -> None:
        valid = sum(rows for _req, _lo, rows in parts)
        # fault-injection site (resilience/faults.py): THE serve drill
        # seam — an injected failure here exercises re-dispatch,
        # circuit transitions, and the flight-recorder trigger exactly
        # as a real runner failure would
        maybe_fail("serve.dispatch")
        # per-request phase marks (armed requests only): staging is
        # the assemble below, device is the runner call — both accrue
        # to every request the micro-batch carries (that IS each
        # request's experience of its shared batch); anything between
        # marks lands in the coalesce remainder, so the breakdown
        # always sums to the end-to-end latency
        track = any(req.timeline is not None
                    for req, _lo, _rows in parts)
        t0 = time.perf_counter() if track else 0.0
        inputs = self._assemble(parts, valid)
        t1 = time.perf_counter() if track else 0.0
        fill = valid / self.chunk
        attrs = {"rows": valid, "requests": len(parts),
                 "fill": round(fill, 3), "model": self.name}
        phases = None
        if track:
            rids = [req.rid for req, _lo, _rows in parts
                    if req.timeline is not None]
            # the flow STEP: every request in this batch links its
            # enqueue span to this dispatch slice (split requests get
            # one step per micro-batch — one connected flow)
            attrs.update(request_ids=rids, flow_ids=rids, flow_ph="t")
            if getattr(self.runner, "supports_phases", False):
                phases = ChunkPhases()
        t2 = time.perf_counter() if track else 0.0
        # the swap gate: a registry weight flip (fleet/registry.py)
        # waits for this dispatch to finish and lands before the next
        # one starts — the zero-downtime hot-swap's atomicity seam
        with self._swap_gate, span("dispatch", lane="serve", **attrs):
            if phases is not None:
                out = self.runner.run(inputs, phases=phases)
            else:
                out = self.runner.run(inputs)
        t3 = time.perf_counter() if track else 0.0
        if track:
            for req, _lo, _rows in parts:
                if req.timeline is not None:
                    req.timeline.add_batch(t1 - t0, t3 - t2,
                                           detail=phases)
        batch_lo = 0
        completed: List[Request] = []
        for req, req_lo, rows in parts:
            w0 = time.perf_counter() if req.timeline is not None \
                else 0.0
            if req.write(out, batch_lo, req_lo, rows):
                completed.append(req)
            if req.timeline is not None:
                req.timeline.add_reassembly(time.perf_counter() - w0)
            batch_lo += rows
        done_t = time.perf_counter()
        slo = slo_tracker()
        rlog = request_log()
        for req in completed:
            lat = done_t - req.submitted
            tl = req.timeline
            if tl is not None:
                rec = tl.finish(done_t, "ok")
                # the worst-case exemplar: request_id + phase
                # breakdown, retained bounded in the reservoir so the
                # scraped p99 resolves to an actual request/trace
                self.metrics.observe_latency(
                    lat, exemplar=tl.exemplar(rec))
                rlog.record(rec, submitted=tl.submitted)
            else:
                self.metrics.observe_latency(lat)
            # the latency population: successes only; failures live in
            # the availability stream (obs/slo.py)
            slo.record(latency_s=lat, ok=True)
        self.metrics.add_batch(valid, self.chunk)

    def _assemble(self, parts, valid: int) -> Dict[str, np.ndarray]:
        """The micro-batch's device inputs: a single request already
        spanning the full chunk passes through as plain views (the
        zero-copy fast path — the runner ships contiguous full chunks
        without staging); everything else coalesces through the
        session's persistent ``PadStaging`` buffers (``stage_parts``
        writes each request's rows consecutively and zero-pads the
        tail), so steady-state serving allocates nothing per batch."""
        sig = self.runner.model_fn.input_signature
        if len(parts) == 1 and valid == self.chunk:
            req, lo, rows = parts[0]
            views = {k: req.inputs[k][lo:lo + rows] for k in sig}
            if all(v.flags.c_contiguous for v in views.values()):
                return views
        return {
            k: self._staging.stage_parts(
                k, [req.inputs[k][lo:lo + rows]
                    for req, lo, rows in parts], self.chunk)
            for k in sig}

    # -- lifecycle -----------------------------------------------------------

    def close(self, drain: bool = True) -> None:
        """Stop admissions; drain (default) or discard the queue; join
        the dispatcher. The engine quiesce discipline: bounded by
        ``drain_timeout_s`` and LOUD on failure, never a hang or a
        silent swallow."""
        abandoned = self._queue.close(drain)
        for req in abandoned:
            if req.fail(ServerClosed(
                    f"server closed before this request was dispatched "
                    f"(model {self.name!r})")):
                # an accepted-then-abandoned request is an availability
                # event too — the caller was promised an answer
                slo_tracker().record(ok=False)
                self._record_outcome(req, "closed")
        # read the dispatcher handle under the lock (a submit() racing
        # this close may be swapping a fresh thread in via
        # _ensure_worker); the join itself stays outside the hold
        with self._lock:
            worker = self._worker
        if worker is not None and worker.is_alive():
            worker.join(self.config.drain_timeout_s)
            if worker.is_alive():
                logger.warning(
                    "serve session %r did not drain within %.1fs; "
                    "dispatcher left running (daemon)", self.name,
                    self.config.drain_timeout_s)
        # final metrics publish: rows/rejections admitted after the
        # dispatcher's last per-batch publish (or never dispatched at
        # all under drain=False) must land in the registry — the last
        # partial window is part of the record, not a rounding error
        self.metrics.publish(default_registry())
        slo_tracker().publish_due(default_registry(), force=True)

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        # the dispatcher thread, lock, and warm staging buffers are
        # process-local; the runner/config/metrics carry their own
        # drop-and-recreate hooks. The queue ships empty (in-flight
        # futures are process-local by nature) but keeps its closing
        # flag — a closed server stays closed across the wire.
        state = self.__dict__.copy()
        del state["_lock"]
        del state["_worker"]
        del state["_staging"]
        del state["_swap_gate"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._worker = None     # restarts lazily on first submit
        self._staging = PadStaging()
        self._swap_gate = threading.Lock()


class ModelServer:
    """Thread-safe online inference server over registered model
    sessions (module docstring; user guide: docs/SERVING.md)."""

    def __init__(self, config: Optional[ServeConfig] = None):
        self.config = config or ServeConfig()
        self.metrics = ServeMetrics()
        self._sessions: Dict[str, ModelSession] = {}
        self._closed = False
        self._lock = threading.Lock()
        self._telemetry = None
        self._started = time.perf_counter()
        # the flight recorder's serve section is built from live
        # servers (weakly held); env-armed processes also get their
        # SIGUSR2 trigger + span retention installed here
        flight.register_server(self)
        flight.autoarm()

    # -- registry ------------------------------------------------------------

    def register(self, name: str, model_fn=None, *, runner=None,
                 batch_size: int = 64, mesh=None,
                 max_inflight: Optional[int] = None) -> ModelSession:
        """Register a model under ``name``: either a ``ModelFunction``
        (a ``BatchRunner`` is built; pass ``mesh`` for a data-parallel
        ``ShardedBatchRunner`` — ``batch_size`` is then PER-CHIP) or a
        prebuilt runner. ``max_inflight`` passes through to the runner
        (runtime/runner.py: the in-flight window's depth). Returns the
        session (for per-model warmup / introspection)."""
        if (model_fn is None) == (runner is None):
            raise ValueError(
                "register() takes exactly one of model_fn= or runner=")
        if runner is None:
            if mesh is not None:
                runner = ShardedBatchRunner(
                    model_fn, mesh=mesh, batch_size=batch_size,
                    max_inflight=max_inflight)
            else:
                runner = BatchRunner(
                    model_fn, batch_size=batch_size,
                    max_inflight=max_inflight)
        elif mesh is not None:
            raise ValueError(
                "pass mesh= with model_fn=, not with a prebuilt "
                "runner (build the ShardedBatchRunner yourself)")
        session = ModelSession(name, runner, self.config, self.metrics)
        with self._lock:
            if self._closed:
                raise ServerClosed(
                    "cannot register on a closed server")
            if name in self._sessions:
                raise ValueError(f"model {name!r} already registered")
            self._sessions[name] = session
        return session

    def session(self, model: Optional[str] = None) -> ModelSession:
        """The named session; with one registered model, the default."""
        with self._lock:
            if not self._sessions:
                raise ValueError("no models registered")
            if model is None:
                if len(self._sessions) > 1:
                    raise ValueError(
                        f"multiple models registered "
                        f"({sorted(self._sessions)}); pass model=")
                return next(iter(self._sessions.values()))
            try:
                return self._sessions[model]
            except KeyError:
                raise ValueError(
                    f"unknown model {model!r}; registered: "
                    f"{sorted(self._sessions)}") from None

    # -- the front door ------------------------------------------------------

    def submit(self, inputs: Dict[str, np.ndarray],
               deadline: Optional[float] = None,
               model: Optional[str] = None,
               priority: int = 0) -> Future:
        """Submit one request: ``{name: [n, *row_shape]}`` host arrays
        → Future resolving to ``{name: [n, *out_shape]}``. ``deadline``
        is seconds from now; a request still queued past it fails with
        ``DeadlineExceeded`` BEFORE any device time is spent. A full
        queue raises ``ServerOverloaded`` immediately (backpressure);
        ``priority`` is the SLO admission class — saturation sheds
        lowest-priority-first, so latency-critical tenants submit with
        a higher class (docs/RESILIENCE.md)."""
        return self.session(model).submit(inputs, deadline,
                                          priority=priority)

    def warmup(self) -> Dict[str, bool]:
        """Pre-trace every registered session at its device batch
        shape (per-session result: False = nothing to warm, e.g. host
        backend) so no first request pays a compile."""
        with self._lock:
            sessions = list(self._sessions.values())
        return {s.name: s.warmup() for s in sessions}

    # -- the health surface --------------------------------------------------

    def telemetry_status(self) -> dict:
        """Per-model operating state for ``/statusz`` and the flight
        recorder's bundles: queue depth, warmup state, runner
        config, and the cumulative serve metrics — everything
        an operator needs to tell "busy" from "wedged" without
        attaching a debugger."""
        with self._lock:
            sessions = dict(self._sessions)
            closed = self._closed
        return {
            "closed": closed,
            "uptime_s": round(time.perf_counter() - self._started, 3),
            "config": {
                "max_wait_s": self.config.max_wait_s,
                "max_queue_rows": self.config.max_queue_rows,
                "default_deadline_s": self.config.default_deadline_s,
                "drain_timeout_s": self.config.drain_timeout_s,
            },
            "models": {
                name: {
                    "queue_rows": s._queue.depth(),
                    "queue_closing": s._queue.closing,
                    "warmed": s.warmed,
                    "collective": s.collective,
                    "chunk": s.chunk,
                    # the LIVE coalesce window (autotune may have
                    # moved it off config.max_wait_s)
                    "max_wait_s": s.max_wait_s,
                    # the breaker's live verdict (docs/RESILIENCE.md):
                    # state/consecutive_failures/opens — how an
                    # operator tells "shedding by design" from "wedged"
                    "circuit": s.circuit.status(),
                    "retry": {
                        "attempts": s.retry_policy.attempts,
                        "budget_tokens": round(
                            s.retry_policy.tokens, 2),
                    },
                    "runner": {
                        "type": type(s.runner).__name__,
                        "max_inflight": getattr(s.runner,
                                                "max_inflight", None),
                        "batch_size": getattr(s.runner, "batch_size",
                                              None),
                    },
                } for name, s in sessions.items()},
            "metrics": self.metrics.as_dict(),
            # the scraped p99's worst-case specimens: request_id +
            # phase breakdown, bounded retention (obs/registry.py
            # Reservoir exemplars) — how a number on a dashboard
            # resolves to an actual slow request
            "latency_exemplars": self.metrics.latency_exemplars(),
        }

    def serve_telemetry(self, port: int = 0, host: str = "127.0.0.1"):
        """Attach the localhost health surface
        (:class:`~sparkdl_tpu.obs.export.TelemetryServer`): started
        immediately, scoped to this server's ``/statusz``, closed with
        the server. ``port=0`` lets the OS pick — read ``.port`` on
        the returned endpoint."""
        from sparkdl_tpu.obs.export import TelemetryServer
        with self._lock:
            if self._closed:
                raise ServerClosed(
                    "cannot attach telemetry to a closed server")
            if self._telemetry is not None:
                return self._telemetry
            tel = TelemetryServer(port=port, host=host,
                                  model_server=self).start()
            # set only after a successful bind+start: a port-in-use
            # failure must not leave a dead endpoint cached
            self._telemetry = tel
            return tel

    # -- lifecycle -----------------------------------------------------------

    def close(self, drain: bool = True) -> None:
        """Stop admissions on every session, then drain (default) or
        discard their queues and join the dispatchers — idempotent."""
        with self._lock:
            self._closed = True
            sessions = list(self._sessions.values())
            telemetry, self._telemetry = self._telemetry, None
        for s in sessions:
            s.close(drain)
        # the final-window publish (each session also published on its
        # own close; this covers the zero-session server, idempotently)
        self.metrics.publish(default_registry())
        slo_tracker().publish_due(default_registry(), force=True)
        if telemetry is not None:
            telemetry.close()

    def __enter__(self) -> "ModelServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close(drain=exc_type is None)
        return False

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        # workers/locks/queue contents drop (inside each session's own
        # hooks), and so does an attached telemetry endpoint (sockets
        # are process-local); config, registered runners, and
        # cumulative metrics values travel
        state = self.__dict__.copy()
        del state["_lock"]
        state["_telemetry"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        # a deserialized server re-registers with the RECEIVING
        # process's flight recorder (bundle coverage follows the
        # process, the H3 singleton discipline)
        flight.register_server(self)
