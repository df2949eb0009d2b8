"""Local partition-parallel execution engine.

The seam where Spark executors + TensorFrames sat in the reference
(SURVEY §1 L0/L1). Host stages (decode, resize, Arrow shuffling) run
concurrently in a thread pool — the analogue of Spark python workers —
while device stages (jitted TPU applies) are serialized behind a lock so
a single accelerator sees one batch stream and HBM isn't oversubscribed
by concurrent partitions. Results stream back in partition order.

Plans containing a re-chunkable device stage (row-preserving,
index-free, with a ``Stage.batch_hint``) execute in two phases: the
host prefix runs per-partition in the pool as always, then the ordered
partition stream flows through the remaining stages on the consumer
thread — the device stage is fed batch-hint-aligned row blocks that
SPAN partition boundaries (outputs re-sliced back to the original
partitions), so partitions smaller than the static device batch stop
padding it. TensorFrames never had this problem (its blocks were
whatever size the partition was); static-shape XLA makes batch
alignment the engine's job rather than the user's.

With ``pipeline_workers >= 2`` (ctor arg or
``SPARKDL_TPU_PIPELINE_WORKERS``; typos degrade to serial) the host
prefix instead runs on the parallel host pipeline
(``data/pipeline.py``): a process pool (thread fallback where the plan
is not pickle-safe) executes source load + decode per partition, hands
fragments back through shared-memory Arrow buffers, and an ordered
bounded re-merge feeds the same consumer-thread re-chunk/ship path —
decode then OVERLAPS ship/dispatch instead of serializing with it
(ROADMAP item 3, the tf.data shape; docs/PERFORMANCE.md "Parallel
host pipeline").

A Spark/mapInArrow binding can replace this class behind the same
``execute(sources, plan)`` contract when pyspark is available (there,
one partition per task — the hint is advisory; see spark_binding).
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
import types
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Iterator, Optional, Sequence, Tuple

import pyarrow as pa

from sparkdl_tpu.obs import default_registry, span
from sparkdl_tpu.resilience.errors import (
    default_retryable_exceptions,
    is_deterministic_jax_error,
)
from sparkdl_tpu.resilience.faults import maybe_fail
from sparkdl_tpu.resilience.policy import RetryPolicy

# NOTE: the retryable classification moved to resilience/errors.py (one
# shared Transient-vs-Permanent split for the engine AND the serve
# layer); `default_retryable_exceptions` / `is_deterministic_jax_error`
# stay importable from this module for existing callers.

logger = logging.getLogger(__name__)

#: the engine's retry pacing: short backoff (partition re-runs are
#: batch work racing nothing), generous budget (ratio 1.0 bounds
#: sustained amplification at 2x offered load — the serve layer's
#: latency-sensitive 0.2 would starve long scans with sparse
#: transients)
ENGINE_RETRY_BASE_BACKOFF_S = 0.02
ENGINE_RETRY_MAX_BACKOFF_S = 1.0
ENGINE_RETRY_BUDGET_RATIO = 1.0
ENGINE_RETRY_BUDGET_CAP = 16.0


def _concat_batches(frags: Sequence[pa.RecordBatch]) -> pa.RecordBatch:
    if len(frags) == 1:
        return frags[0]
    tbl = pa.Table.from_batches(frags).combine_chunks()
    batches = tbl.to_batches()
    if len(batches) == 1:
        return batches[0]
    # combine_chunks yields one chunk per column for any sane size; a
    # >2GB column can still split. Returning a subset would silently
    # drop rows and corrupt the re-chunk bookkeeping — fail loudly if
    # no true concat exists.
    if hasattr(pa, "concat_batches"):
        return pa.concat_batches(batches)
    raise RuntimeError(
        f"cannot concatenate {len(batches)} oversized Arrow chunks on "
        "this pyarrow build; reduce the device batch_hint or partition "
        "size")


def _take_rows(frags: list, n: int) -> pa.RecordBatch:
    """Remove and return the first ``n`` rows from a fragment list
    (zero-copy slices; a copy only when a block spans fragments)."""
    take = []
    taken = 0
    while taken < n:
        b = frags[0]
        need = n - taken
        if b.num_rows <= need:
            take.append(b)
            taken += b.num_rows
            frags.pop(0)
        else:
            take.append(b.slice(0, need))
            frags[0] = b.slice(need)
            taken = n
    return _concat_batches(take)


class _OrderedPartitions:
    """``LocalEngine._execute_indexed``'s stream: ``(logical_index,
    batch)`` in partition order, and a look one partition ahead that
    never waits. The window of futures lives in ``state``, shared with
    the generator that fills and drains it; both run on the consumer's
    thread, so neither sees the other half-way. The generator is held
    here alone, so dropping the stream finalizes it (its ``finally``
    cancels what is in flight) as promptly as it did bare."""

    def __init__(self, gen, state, logical):
        self._gen = gen
        self._state = state
        self._logical = logical

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def close(self):
        self._gen.close()

    @property
    def drained(self) -> bool:
        """Every partition has been handed out."""
        return self._state.next_to_yield >= self._state.count

    def take_ready(self):
        """The next partition if its future is already done and holds
        a result, else None. Submits nothing: the window refills only
        when the consumer asks for a partition the usual way, so a
        stream that is dropped early has loaded no more than before. A
        partition that failed stays where it is and raises when it is
        asked for in its turn."""
        st = self._state
        pos = st.next_to_yield
        fut = st.pending.get(pos)
        if fut is None or not fut.done() or fut.cancelled() \
                or fut.exception() is not None:
            return None
        del st.pending[pos]
        st.next_to_yield = pos + 1
        return self._logical(pos), fut.result()


class LocalEngine:
    """Thread-pool engine with ordered streaming and bounded in-flight
    partitions (backpressure keeps memory flat on large frames).

    Transient failures are retried ``max_retries`` times before
    propagating — the counterpart of Spark's task retry, which gave the
    reference free retry of inference partitions (SURVEY §5 "failure
    detection"). Retry runs on the shared
    :class:`~sparkdl_tpu.resilience.policy.RetryPolicy` (bounded
    attempts, exponential backoff with deterministic jitter, a retry
    budget bounding sustained amplification; each granted retry counts
    ``engine.retries``). The retryable set defaults to
    :func:`default_retryable_exceptions` (IO + jax/PJRT transients +
    the typed ``TransientError`` family) and is configurable via
    ``retryable_exceptions``. Deterministic errors (bad column names,
    shape mismatches, jax statuses a re-run cannot fix) propagate
    immediately and unchanged.
    """

    def __init__(self, num_workers: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 max_retries: int = 2,
                 stage_metrics=None,
                 retryable_exceptions: Optional[Tuple[type, ...]] = None,
                 pipeline_workers: Optional[int] = None,
                 pipeline_read_ahead: Optional[int] = None,
                 pipeline_mode: Optional[str] = None,
                 inputsvc_endpoints=None):
        self.num_workers = num_workers or min(32, (os.cpu_count() or 4))
        # the parallel host pipeline (data/pipeline.py): >= 2 resolved
        # workers select the pooled streaming mode per execute() —
        # source load + the host-stage prefix run on N pool workers
        # with an ordered bounded re-merge, so decode overlaps
        # ship/dispatch instead of serializing with it. 0/1 (and env
        # typos) = the serial path below, unchanged. Both knobs are
        # plain int attributes re-read at each execute()/wave — the
        # autotune controller's PipelineTarget moves them live
        # (single attribute stores, the repo-wide apply discipline).
        from sparkdl_tpu.data.pipeline import (
            resolve_mode,
            resolve_read_ahead,
            resolve_workers,
        )
        self.pipeline_workers = resolve_workers(pipeline_workers)
        self.pipeline_read_ahead = resolve_read_ahead(
            pipeline_read_ahead, self.pipeline_workers)
        self.pipeline_mode = resolve_mode(pipeline_mode)
        # the disaggregated decode fleet (sparkdl_tpu/inputsvc;
        # docs/DATA_SERVICE.md): configured endpoints route the host
        # prefix to remote DecodeServers per execute(), with loud
        # local fallback when the fleet is unreachable.
        # ``inputsvc_workers`` is the LIVE fan-out width — a plain int
        # attribute re-read per execute, so the autotune controller's
        # PipelineTarget can move it like the pipeline knobs
        from sparkdl_tpu.inputsvc.client import resolve_endpoints
        self.inputsvc_endpoints = resolve_endpoints(inputsvc_endpoints)
        self.inputsvc_workers = len(self.inputsvc_endpoints)
        self._pipeline = None           # lazily-built HostPipeline
        self._pipeline_lock = threading.Lock()
        # Enough in-flight partitions to keep workers busy while the
        # consumer drains in order. A falsy sentinel (0/None) is NOT an
        # explicit window: treating 0 as explicit would disable the
        # adaptive widening while the `or` fallback discarded the 0
        # itself — the engine would honor a value the caller never got.
        self._explicit_inflight = (max_inflight is not None
                                   and max_inflight > 0)
        self.max_inflight = (max_inflight if self._explicit_inflight
                             else self.num_workers * 2)
        self.max_retries = max_retries
        # normalize to tuple: `except` rejects lists/sets at failure
        # time (masking the real error); an explicit () means "retry
        # nothing" and must not fall back to the defaults
        self.retryable_exceptions = (
            tuple(retryable_exceptions) if retryable_exceptions is not None
            else default_retryable_exceptions())
        # optional sparkdl_tpu.utils.StageMetrics for per-stage timing
        self.stage_metrics = stage_metrics
        # ONE policy per engine, shared by every pool worker and the
        # consumer-thread stream stages: the budget only bounds retry
        # amplification if the retrying threads share the bucket
        # (resilience/policy.py)
        self.retry_policy = RetryPolicy(
            attempts=1 + max(0, self.max_retries),
            base_backoff_s=ENGINE_RETRY_BASE_BACKOFF_S,
            max_backoff_s=ENGINE_RETRY_MAX_BACKOFF_S,
            budget_ratio=ENGINE_RETRY_BUDGET_RATIO,
            budget_cap=ENGINE_RETRY_BUDGET_CAP,
            retryable=self._retryable)
        self._pool = ThreadPoolExecutor(
            max_workers=self.num_workers,
            thread_name_prefix="sparkdl-tpu-host")
        self._device_lock = threading.Lock()

    def _retryable(self, exc: BaseException) -> bool:
        """The engine's retry classifier: inside the configured
        exception set AND not a deterministic jax status (re-running a
        program whose shapes are wrong just triples time-to-failure)."""
        return (isinstance(exc, self.retryable_exceptions)
                and not is_deterministic_jax_error(exc))

    # Locks and thread pools don't pickle; frames normally drop their
    # engine before shipping (frame.Source pickles engine=None), but an
    # engine reachable through any other closure must survive the wire
    # the same way — fresh pool, fresh lock, zero in-flight state on
    # arrival (the sparkdl-lint H3 contract).
    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_pool"]
        del state["_device_lock"]
        # the host-pipeline pool follows the same H3 contract: pools
        # and their lock drop on the wire; the pipeline_workers /
        # read_ahead / mode CONFIG travels, so a shipped engine
        # rebuilds an equivalent pool on first pooled execute
        del state["_pipeline"]
        del state["_pipeline_lock"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._pool = ThreadPoolExecutor(
            max_workers=self.num_workers,
            thread_name_prefix="sparkdl-tpu-host")
        self._device_lock = threading.Lock()
        self._pipeline = None
        self._pipeline_lock = threading.Lock()

    def _run_stage(self, stage, batch, index, timings,
                   upcoming=None) -> pa.RecordBatch:
        # fault-injection site (resilience/faults.py; disarmed: one
        # armed-check): every stage apply, pooled and stream paths
        maybe_fail("engine.stage_apply")
        # every stage call lands on the tracer's "engine" lane
        # (obs/trace.py — a no-op when SPARKDL_TPU_TRACE is unset)
        with span(f"stage:{stage.name}", lane="engine",
                  rows=batch.num_rows, kind=stage.kind):
            t0 = time.perf_counter()
            if upcoming is not None:
                out = stage.fn(batch, upcoming=upcoming)
            else:
                out = (stage.fn(batch, index) if stage.with_index
                       else stage.fn(batch))
            dt = time.perf_counter() - t0
        if stage.kind != "device":
            # the utilization ledger's decode-lane feed (obs/ledger.py):
            # host-stage busy time — device-stage applies wrap
            # runner.run, which feeds device.run_seconds itself, so
            # counting them here would double-attribute the window
            default_registry().counter("engine.busy_seconds").add(dt)
        if timings is not None:
            timings.append((stage.name, dt, batch.num_rows))
        return out

    def _run_once(self, source, plan, index) -> pa.RecordBatch:
        # Buffer stage timings locally and flush only on success, so a
        # retried partition doesn't double-count its completed stages.
        timings = [] if self.stage_metrics is not None else None
        # fault-injection site: the partition's source read (the
        # worker-death drill for ROADMAP item 1's multi-host plan)
        maybe_fail("engine.source_load")
        with span("source.load", lane="engine", partition=index):
            t0 = time.perf_counter()
            batch = source.load()
            # source reads (decode/IO) are decode-lane busy time too
            default_registry().counter("engine.busy_seconds").add(
                time.perf_counter() - t0)
        for stage in plan:
            if stage.kind == "device":
                with self._device_lock:
                    batch = self._run_stage(stage, batch, index, timings)
            else:
                batch = self._run_stage(stage, batch, index, timings)
        if timings:
            for name, seconds, rows in timings:
                self.stage_metrics.add(name, seconds, rows)
        return batch

    def _run_partition(self, source, plan, index) -> pa.RecordBatch:
        # with_index stages see the partition's logical identity, not
        # its position in a reordered/subset frame (frame.Source)
        logical = getattr(source, "logical_index", None)
        if logical is not None:
            index = logical
        # the shared RetryPolicy owns attempts/backoff/budget
        # (resilience/policy.py): a transient partition failure
        # re-runs cleanly from its source; deterministic errors and
        # budget exhaustion propagate typed
        return self.retry_policy.call(
            lambda: self._run_once(source, plan, index),
            key=f"partition:{index}",
            on_retry=self._log_retry(f"partition {index}"))

    def _log_retry(self, what: str):
        def on_retry(attempt, exc, delay_s):
            default_registry().counter("engine.retries").add()
            logger.warning(
                "%s attempt %d/%d failed (%s); retrying in %.3fs",
                what, attempt, 1 + max(0, self.max_retries), exc,
                delay_s)
        return on_retry

    @staticmethod
    def _rechunkable(stage) -> bool:
        """Whether the engine may feed this stage row blocks cut at its
        ``batch_hint`` instead of per-partition blocks (see Stage
        docstring): device stages that preserve rows 1:1 and don't
        depend on partition identity."""
        return (stage.kind == "device" and stage.row_preserving
                and not stage.with_index
                and bool(getattr(stage, "batch_hint", None)))

    def execute(self, sources: Sequence, plan: Sequence) -> Iterator[pa.RecordBatch]:
        """Yield transformed partition batches in partition order, running
        at most ``max_inflight`` partitions concurrently.

        Plans whose tail contains a re-chunkable device stage split into
        two phases: the host prefix runs per-partition in the pool (as
        always), then the ordered partition stream flows through the
        remaining stages on the consumer thread, with re-chunkable
        device stages fed batch-hint-aligned row blocks that span
        partition boundaries — small partitions stop padding the static
        device shape — and their outputs re-sliced to the original
        partition boundaries (row identity and order unchanged)."""
        if not sources:
            return iter(())
        plan = list(plan)
        if self.inputsvc_endpoints and int(self.inputsvc_workers
                                           or 0) >= 1:
            # the disaggregated decode fleet (sparkdl_tpu/inputsvc):
            # the host prefix runs on remote DecodeServers; returns
            # None when no endpoint answers (counted + warned) and
            # the local paths below take over unchanged
            remoted = self._execute_remote(sources, plan)
            if remoted is not None:
                return remoted
        if int(self.pipeline_workers or 0) >= 2:
            # the parallel host pipeline (data/pipeline.py): the
            # source-load + host-stage prefix runs on N pool workers
            # with an ordered bounded re-merge; returns None when the
            # pool degrades to serial (1-core auto mode, config typo)
            # and the unchanged path below takes over
            pipelined = self._execute_pipelined(sources, plan)
            if pipelined is not None:
                return pipelined
        split = next((i for i, st in enumerate(plan)
                      if self._rechunkable(st)), None)
        if split is None:
            return (b for _, b in self._execute_indexed(sources, plan))
        # While the consumer blocks in a device call, the pool keeps
        # loading partitions ahead — the window must cover a device
        # chunk's worth of SMALL partitions or decode stalls behind the
        # device. The window grows ADAPTIVELY: the first re-chunk stage measures actual
        # partition rows against its hint and widens the box up to 16 —
        # large (already-aligned) partitions never pay extra buffering;
        # an explicit ctor max_inflight is respected as given.
        inflight_box = [self.max_inflight]
        hints = [int(st.batch_hint) for st in plan[split:]
                 if self._rechunkable(st)]
        stream = self._execute_indexed(sources, plan[:split],
                                       inflight_box=inflight_box)
        first = True
        for stage in plan[split:]:
            if self._rechunkable(stage):
                widen = first and not self._explicit_inflight
                stream = self._stream_rechunk(
                    stream, stage,
                    inflight_box=inflight_box if widen else None,
                    max_hint=max(hints))
                first = False
            elif stage.kind == "device":
                stream = self._stream_plain(stream, stage)
            else:
                # host stages downstream of the device stage keep pool
                # parallelism (ordered futures) so device dispatch never
                # waits on host post-processing
                stream = self._stream_pooled(stream, stage)
        return (b for _, b in stream)

    def _host_pipeline(self):
        from sparkdl_tpu.data.pipeline import HostPipeline
        with self._pipeline_lock:
            if self._pipeline is None:
                self._pipeline = HostPipeline(mode=self.pipeline_mode)
            return self._pipeline

    def _execute_remote(self, sources: Sequence, plan: Sequence
                        ) -> Optional[Iterator[pa.RecordBatch]]:
        """The decode-fleet streaming mode (sparkdl_tpu/inputsvc): the
        plan's host prefix runs on remote DecodeServers with an
        ordered re-merge; the fragment stream then flows through the
        same consumer-thread stage machinery as the pooled/serial
        paths. ``inputsvc_workers`` bounds the fan-out width (the
        autotune knob); None — nothing picklable, or no endpoint
        reachable — falls through to the local paths, loudly
        (``inputsvc.fallbacks``)."""
        from sparkdl_tpu.inputsvc.client import RemotePipeline
        width = max(1, int(self.inputsvc_workers))
        dsplit = next((i for i, st in enumerate(plan)
                       if st.kind == "device"), len(plan))
        stream = RemotePipeline(
            self.inputsvc_endpoints[:width]).stream(
                sources, plan[:dsplit], self)
        if stream is None:
            return None
        hints = [int(st.batch_hint) for st in plan[dsplit:]
                 if self._rechunkable(st)]
        for stage in plan[dsplit:]:
            if self._rechunkable(stage):
                stream = self._stream_rechunk(stream, stage,
                                              max_hint=max(hints))
            elif stage.kind == "device":
                stream = self._stream_plain(stream, stage)
            else:
                stream = self._stream_pooled(stream, stage)
        return (b for _, b in stream)

    def _execute_pipelined(self, sources: Sequence, plan: Sequence
                           ) -> Optional[Iterator[pa.RecordBatch]]:
        """The pooled streaming mode (data/pipeline.py): the plan's
        host prefix — everything before the FIRST device stage — runs
        per-partition on the worker pool; the ordered fragment stream
        then flows through the same consumer-thread stage machinery as
        the serial path (re-chunkable device stages get hint-aligned
        blocks, downstream host stages keep thread-pool parallelism).
        Returns None when the pool resolves to serial — the caller
        falls through to the unchanged single-stream path."""
        from sparkdl_tpu.data import pipeline as host_pipeline
        workers = host_pipeline.effective_workers(
            int(self.pipeline_workers), self.pipeline_mode)
        if workers < 2:
            return None
        dsplit = next((i for i, st in enumerate(plan)
                       if st.kind == "device"), len(plan))
        stream = self._host_pipeline().stream(
            sources, plan[:dsplit], self, workers)
        hints = [int(st.batch_hint) for st in plan[dsplit:]
                 if self._rechunkable(st)]
        for stage in plan[dsplit:]:
            if self._rechunkable(stage):
                # no adaptive inflight widening here: the pipeline's
                # read_ahead knob IS the pooled look-ahead window (an
                # autotuner knob, not a heuristic)
                stream = self._stream_rechunk(stream, stage,
                                              max_hint=max(hints))
            elif stage.kind == "device":
                stream = self._stream_plain(stream, stage)
            else:
                stream = self._stream_pooled(stream, stage)
        return (b for _, b in stream)

    def _execute_indexed(self, sources: Sequence, plan: Sequence,
                         inflight_box: Optional[list] = None
                         ) -> Iterator[Tuple[int, pa.RecordBatch]]:
        """The pooled per-partition path, yielding
        ``(logical_index, batch)`` in partition order. ``inflight_box``
        is a one-element mutable window size a downstream re-chunk
        stage may widen once it has seen real partition sizes."""
        box = inflight_box or [self.max_inflight]
        # Drain in-flight siblings on exit only when the plan OR a
        # source has side effects: a straggler _write_part re-creating
        # write_parquet's just-swept staging dir AFTER cleanup ran
        # corrupts the cleanup's outcome — and cache_to_disk spill
        # sources write IPC files inside Source.load, so a straggler
        # LOAD can equally re-create spill files after the
        # tuning-cleanup rmtree (ADVICE r5). Pure plans over pure
        # sources cancel-only — take(1)/first() on a decode-heavy
        # frame must not block for a whole in-flight wave of partition
        # decodes (review r5).
        drain = (any(getattr(st, "effectful", False) for st in plan)
                 or any(getattr(src, "effectful", False)
                        for src in sources))

        def _logical(pos: int) -> int:
            logical = getattr(sources[pos], "logical_index", None)
            return pos if logical is None else logical

        state = types.SimpleNamespace(
            pending={}, next_to_submit=0, next_to_yield=0,
            count=len(sources))

        def _gen():
            st, n = state, state.count
            pending: dict[int, Future] = st.pending
            try:
                while st.next_to_yield < n:
                    while (st.next_to_submit < n
                           and len(pending) < box[0]):
                        fut = self._pool.submit(
                            self._run_partition,
                            sources[st.next_to_submit],
                            plan, st.next_to_submit)
                        pending[st.next_to_submit] = fut
                        st.next_to_submit += 1
                    # (take_ready may have moved next_to_yield on
                    # while this generator was suspended)
                    pos = st.next_to_yield
                    fut = pending.pop(pos)
                    st.next_to_yield = pos + 1
                    yield _logical(pos), fut.result()
            finally:
                for fut in pending.values():
                    fut.cancel()
                if drain:
                    # QUIESCE before returning control: a running task
                    # can't be cancelled and would otherwise keep
                    # producing side effects AFTER the caller's
                    # cleanup ran
                    for fut in pending.values():
                        if not fut.cancelled():
                            try:
                                fut.result()
                            except Exception as drain_err:
                                # the primary error is already
                                # propagating; record the secondary
                                # one instead of masking the drain
                                logger.debug(
                                    "quiesce drain error: %s",
                                    drain_err)

        return _OrderedPartitions(_gen(), state, _logical)

    # -- stream phase (consumer thread) --------------------------------------

    def _apply_stream_stage(self, stage, batch, index,
                            upcoming=None) -> pa.RecordBatch:
        """Run one stage call on the consumer thread with the same
        retry/metrics semantics as the pooled path (the shared
        RetryPolicy). Retrying here is pure: the input block is
        already materialized (no source re-load), and stage fns are
        pure by the plan contract. ``upcoming`` is the look-ahead of a
        ``Stage.with_upcoming`` stage (``_stream_rechunk``)."""
        def once():
            timings = [] if self.stage_metrics is not None else None
            if stage.kind == "device":
                with self._device_lock:
                    out = self._run_stage(stage, batch, index, timings,
                                          upcoming)
            else:
                out = self._run_stage(stage, batch, index, timings)
            if timings:
                for name, seconds, rows in timings:
                    self.stage_metrics.add(name, seconds, rows)
            return out

        return self.retry_policy.call(
            once, key=f"stream:{stage.name}",
            on_retry=self._log_retry(f"stream stage {stage.name}"))

    def _stream_plain(self, stream, stage):
        for idx, batch in stream:
            yield idx, self._apply_stream_stage(stage, batch, idx)

    def _stream_pooled(self, stream, stage):
        """Host stages downstream of a re-chunked device stage, run in
        the pool with a bounded ordered future window (tasks are
        independent units, so sharing the pool with the upstream prefix
        cannot deadlock)."""
        pending: collections.deque = collections.deque()
        try:
            for idx, batch in stream:
                pending.append((idx, self._pool.submit(
                    self._apply_stream_stage, stage, batch, idx)))
                # >=: the documented bound is AT MOST max_inflight
                # in-flight (submit-then-drain at > held one extra
                # partition's device output beyond the window)
                while len(pending) >= self.max_inflight:
                    i, fut = pending.popleft()
                    yield i, fut.result()
            while pending:
                i, fut = pending.popleft()
                yield i, fut.result()
        finally:
            # same QUIESCE discipline as _execute_indexed, gated the
            # same way: only an EFFECTFUL stage (a _write_part task
            # re-creating write_parquet's just-swept staging dir AFTER
            # the caller's cleanup ran) needs its in-flight siblings
            # drained before control returns; pure stages cancel-only
            # so take(n) stays interactive.
            for _, fut in pending:
                fut.cancel()
            if getattr(stage, "effectful", False):
                for _, fut in pending:
                    if not fut.cancelled():
                        try:
                            fut.result()
                        except Exception as drain_err:
                            # primary error already propagating;
                            # record, don't mask the drain outcome
                            logger.debug("quiesce drain error: %s",
                                         drain_err)

    def _stream_rechunk(self, stream, stage, inflight_box=None,
                        max_hint=None):
        """Feed ``stage`` row blocks cut at multiples of its batch_hint
        from the ordered partition stream; re-slice outputs back to the
        original partition boundaries. Greedy dispatch (all full hints
        available per arrival go in ONE stage call) preserves the
        runner's internal async chunk pipelining for large partitions.

        The hint is re-read BETWEEN blocks (``cur_hint``), not frozen
        at stream start: a ``LiveBatchHint`` whose runner the autotune
        controller moves along its pre-warmed shape ladder
        (``sparkdl_tpu/autotune``) re-aligns the cut mid-stream. Row
        identity and order are hint-independent — the ``segs``
        bookkeeping re-slices outputs to the original partition
        boundaries whatever sizes the blocks were cut at (pinned by
        ``tests/test_autotune.py::TestMidStreamHintChange``).

        **One block of look-ahead** (``Stage.with_upcoming``): each
        block is passed with ``upcoming``, a callable the stage may
        ask, while it works on this block, for the block that comes
        next. The answer is the next cut of the rows already buffered,
        whatever partitions it spans, after taking in every following
        partition whose load has ALREADY finished
        (``_OrderedPartitions.take_ready``); where the stream has no
        such look (the pooled and remote host pipelines, a stage
        chained behind another) or the next partition is still
        loading, it is None and the stage works as it always did. It
        never waits, so block ``k`` is never held for block ``k+1``'s
        host prefix, and it submits nothing, so an early ``close()``
        has loaded what it would have. A block that was announced is
        the next block run, before the stream is asked (and waited)
        for anything more. The stage's runner owns whatever it starts
        for an announced block; ``stage.on_close`` is called when the
        stream ends, however it ends, so nothing started for a block
        that never came stays in flight. Retry, ``_device_lock`` and
        stage metrics stay per block (``_apply_stream_stage``)."""

        def cur_hint() -> int:
            return max(1, int(stage.batch_hint))

        in_frags: list = []      # un-dispatched input fragments
        in_rows = 0
        out_frags: list = []     # stage outputs not yet re-sliced
        out_rows = 0
        # (idx, nrows, out): out is set for an empty partition (its
        # batch, run through the stage when its turn to leave comes)
        segs: collections.deque = collections.deque()
        looks = getattr(stage, "with_upcoming", False)
        take_ready = (getattr(stream, "take_ready", None) if looks
                      else None)
        announced = None         # the block cut ahead of its turn
        ended = False            # the stream has given its last

        def admit(idx, batch):
            nonlocal in_rows, inflight_box
            if inflight_box is not None and batch.num_rows:
                # first real partition: widen the prefix load-ahead
                # window so the pool can cover ~2 device chunks of
                # small partitions while the consumer blocks in a
                # device call (execute() docstring measurement); large
                # partitions leave the window as-is
                need = -(-2 * int(max_hint or cur_hint())
                         // batch.num_rows)
                # widen-only: never shrink an already-deeper default
                # (many-core hosts run num_workers*2 > 16)
                inflight_box[0] = max(inflight_box[0], min(16, need))
                inflight_box = None
            if batch.num_rows == 0:
                segs.append((idx, 0, batch))
            else:
                segs.append((idx, batch.num_rows, None))
                in_frags.append(batch)
                in_rows += batch.num_rows

        def cut():
            """The next block of the buffered rows, or None: all the
            full hints there are (everything, once no partition is
            still to come), except that a whole fragment that is
            itself a hint multiple goes AS-IS — its Arrow buffers
            reach the device stage as zero-copy views (the runner
            stages nothing for aligned contiguous blocks), where
            folding it into one greedy concat with its neighbors would
            re-copy every row. Only misaligned spans concatenate; they
            still go greedily so the runner's internal async chunk
            pipelining is preserved."""
            nonlocal in_rows
            hint = cur_hint()
            final = ended or (take_ready is not None and stream.drained)
            total = in_rows if final else (in_rows // hint) * hint
            if not total:
                return None
            head = in_frags[0]
            n = (head.num_rows if head.num_rows <= total
                 and head.num_rows % hint == 0 else total)
            with span("rechunk.cut", lane="engine", rows=n):
                block = _take_rows(in_frags, n)
            in_rows -= n
            return block

        def look_ahead():
            """Cut the block after this one if its rows are at hand,
            taking in partitions that have already loaded."""
            nonlocal announced
            while announced is None:
                announced = cut()
                nxt = (take_ready() if announced is None
                       and take_ready is not None else None)
                if nxt is None:
                    break
                admit(*nxt)
            return announced

        def run_blocks():
            """Run every block the buffered rows hold, handing out the
            partitions each one completes."""
            nonlocal announced, out_rows
            while True:
                block, announced = announced, None
                if block is None:
                    block = cut()
                if block is None:
                    return
                out = self._apply_stream_stage(
                    stage, block, -1,
                    upcoming=look_ahead if looks else None)
                if out.num_rows != block.num_rows:
                    raise RuntimeError(
                        f"stage {stage.name!r} declared row_preserving "
                        f"but returned {out.num_rows} rows for "
                        f"{block.num_rows}")
                out_frags.append(out)
                out_rows += out.num_rows
                yield from ready()

        def ready():
            nonlocal out_rows
            while segs:
                idx, nrows, out = segs[0]
                if nrows == 0:
                    # empty partitions keep their schema by running
                    # the stage directly (runners short-circuit N=0)
                    out = self._apply_stream_stage(stage, out, idx)
                else:
                    if out_rows < nrows:
                        return
                    out = _take_rows(out_frags, nrows)
                    out_rows -= nrows
                segs.popleft()
                yield idx, out

        try:
            for idx, batch in stream:
                admit(idx, batch)
                yield from run_blocks()
                yield from ready()
            ended = True
            yield from run_blocks()  # the stage pads the tail block
            yield from ready()
            assert not segs, "re-chunk bookkeeping leaked partitions"
        finally:
            on_close = getattr(stage, "on_close", None)
            if on_close is not None:
                on_close()

    def shutdown(self):
        self._pool.shutdown(wait=False, cancel_futures=True)
        with self._pipeline_lock:
            pipeline, self._pipeline = self._pipeline, None
        if pipeline is not None:
            pipeline.shutdown()


_default: Optional[LocalEngine] = None
_default_lock = threading.Lock()


def default_engine() -> LocalEngine:
    global _default
    with _default_lock:
        if _default is None:
            _default = LocalEngine()
        return _default


def set_default_engine(engine: LocalEngine):
    global _default
    with _default_lock:
        _default = engine
