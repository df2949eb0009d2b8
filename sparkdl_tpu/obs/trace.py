"""Span tracer: one shared clock across engine → ship → device.

The pipeline's signals were fragmented — ``StageMetrics`` timed engine
stages, ``RunnerMetrics`` counted ship bytes, ``utils/profiling.trace``
wrapped ``jax.profiler``, and none of them shared a clock — so "the
link moved between measurements" stayed an anecdote (BENCH r05
race_note) instead of a diagnosable timeline. This module is the shared
clock: every layer records ``span(name, lane=...)`` intervals into ONE
process-wide bounded ring buffer, exportable as Chrome/Perfetto
trace-event JSON (open ``Tracer.export``'s output in
``ui.perfetto.dev``).

The clock IS ``time.perf_counter()``: a record's ``start`` and ``end``
are its raw readings, not offsets from a private epoch. Any in-process
profiler window therefore aligns by one subtraction: read
``time.perf_counter()`` where ``jax.profiler.start_trace`` is called
and a span lies at ``start - that reading`` on the device trace's
clock (``utils/profiling.trace`` records that reading beside the
spans it writes; the two zeros agree to 0.05 ms on the v5e).
``spans()`` returns the retained records oldest first and ``dropped``
says whether the ring lost any, so "the spans since a clock reading"
is a filter on ``start``.

Causes: every record carries its own ``span_id`` and the ``parent_id``
of the span that was open on the same thread when it began (0: none)
-- a thread-local stack, touched only when armed. The post-hoc
recorders (``timed_device_get``, the compile log's ``compile`` span,
the request log) take the same parent, so the spans of one partition
share an ancestor, its ``runner.run``/``runner.run_sharded``, and a
span's self time is its length minus its children's.

Arming: ``SPARKDL_TPU_TRACE=1`` in the environment, or
``tracer().arm()`` programmatically (the override wins over the env).
Disarmed, ``span()`` returns one shared no-op context manager — no
allocation, no lock, no ring-buffer growth — so instrumentation can sit
permanently on the hot path (the overhead contract is pinned by
``tests/test_obs.py::test_disarmed_span_overhead``).

Lanes are the pipeline's layers, not threads: ``engine`` (decode /
stage execution / fragment cutting), ``ship`` (staging, dispatch,
device_put, the collective launch lock), ``device`` (the explicit
device_get drain — the only host-observable device-side edge),
``estimator`` (epoch/step loops). The export maps each lane to a
Perfetto process group and each OS thread to a track inside it.

Spans never run at jit trace time: the clock reads happen in host code
around the jitted call, and sparkdl-lint's H2 rule flags any
``span(...)`` placed inside a jit-traced function (it would read the
compile-time wall clock once and freeze it into the program).

Ring-buffer discipline: the buffer is bounded (``capacity`` ctor arg,
default ``SPARKDL_TPU_TRACE_BUFFER`` or 65536 spans); when full the
OLDEST spans evict and :attr:`Tracer.dropped` counts them — the export
carries a visible drop note, never a silent truncation.

Pickle discipline (the ``StageMetrics`` precedent): ``__getstate__``
drops the lock and the ring buffer — a tracer captured in a stage
closure ships armed-ness and capacity, and spans recorded on the
remote side stay remote (driver-side timelines are a LocalEngine
feature, like driver-side metrics).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

_TRUE = ("1", "true", "yes", "on")

#: ring-buffer capacity (spans) when SPARKDL_TPU_TRACE_BUFFER is unset
DEFAULT_CAPACITY = 65536

SpanRecord = collections.namedtuple(
    "SpanRecord",
    ["name", "lane", "thread_id", "thread_name", "start", "end",
     "attrs", "span_id", "parent_id"],
    defaults=(0, 0))

# span ids are process-wide (next() on a count is atomic under the
# interpreter lock); 0 is "no span"
_IDS = itertools.count(1)


class _NoopSpan:
    """The disarmed fast path: one shared instance, nothing allocated,
    nothing recorded."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _NoopSpan()


class _Span:
    """An armed span: records (start, end, thread, attrs, its id and
    its parent's) on exit — including exceptional exit, tagged with
    the exception type, so a failed stage still shows up on the
    timeline. While open it is the parent of whatever begins on its
    thread."""

    __slots__ = ("_tracer", "_name", "_lane", "_attrs", "_start",
                 "_id", "_parent", "_stack")

    def __init__(self, tracer: "Tracer", name: str, lane: str,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._lane = lane
        self._attrs = attrs
        self._start = 0.0
        self._id = self._parent = 0
        self._stack: Optional[List[int]] = None

    def __enter__(self):
        stack = self._stack = self._tracer._open_spans()
        self._id = next(_IDS)
        self._parent = stack[-1] if stack else 0
        stack.append(self._id)
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.perf_counter()
        stack = self._stack
        if stack and stack[-1] == self._id:
            stack.pop()
        elif self._id in stack:
            # closed out of order (a generator suspended inside a span
            # and resumed after its caller's closed): leave the others
            stack.remove(self._id)
        attrs = self._attrs
        if exc_type is not None:
            attrs = dict(attrs, error=exc_type.__name__)
        self._tracer._record(self._name, self._lane, self._start, end,
                             attrs, self._id, self._parent)
        return False


def _env_armed() -> bool:
    return os.environ.get("SPARKDL_TPU_TRACE", "").lower() in _TRUE


class Tracer:
    """Process-wide, thread-safe span recorder with a bounded ring
    buffer and Chrome/Perfetto trace-event export."""

    # sparkdl-lint H3 contract: spans arrive from every engine worker
    # thread at once — all buffer/counter writes hold self._lock
    _lock_guards = ("_appended",)

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            # the module-level singleton parses this at import time —
            # a config typo must degrade to the default, not make
            # `import sparkdl_tpu` unimportable for disarmed runs
            raw = os.environ.get("SPARKDL_TPU_TRACE_BUFFER", "")
            try:
                capacity = int(raw) if raw else DEFAULT_CAPACITY
                if capacity <= 0:
                    raise ValueError(capacity)
            except ValueError:
                import logging
                logging.getLogger(__name__).warning(
                    "SPARKDL_TPU_TRACE_BUFFER=%r is not a positive "
                    "int; using the default %d", raw, DEFAULT_CAPACITY)
                capacity = DEFAULT_CAPACITY
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        # None → follow the env; True/False → programmatic override
        self._override: Optional[bool] = None
        self._lock = threading.Lock()
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self._appended = 0
        # per thread, the ids of the spans open on it, outermost first
        self._local = threading.local()
        # the export's origin: a span's exported timestamp is
        # microseconds since this perf_counter() reading
        self._epoch = time.perf_counter()

    # -- arming --------------------------------------------------------------

    @property
    def armed(self) -> bool:
        ov = self._override
        if ov is not None:
            return ov
        return _env_armed()

    def arm(self) -> None:
        """Record spans regardless of SPARKDL_TPU_TRACE."""
        self._override = True

    def disarm(self) -> None:
        """Stop recording regardless of SPARKDL_TPU_TRACE."""
        self._override = False

    def arm_from_env(self) -> None:
        """Drop any programmatic override; follow the env again."""
        self._override = None

    # -- recording -----------------------------------------------------------

    def span(self, name: str, lane: str = "host", **attrs):
        """Context manager timing the enclosed block into the ring
        buffer; a shared no-op when disarmed."""
        if not self.armed:
            return _NOOP
        return _Span(self, name, lane, attrs)

    def _open_spans(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, name: str, lane: str, start: float, end: float,
                attrs: Dict[str, Any], span_id: int = 0,
                parent_id: Optional[int] = None) -> None:
        """Append one record. A post-hoc recorder (the interval is
        already over: ``timed_device_get``, the compile log) passes no
        ids and gets a fresh one, under the span open on this thread
        now."""
        if parent_id is None:
            stack = self._open_spans()
            parent_id = stack[-1] if stack else 0
        t = threading.current_thread()
        rec = SpanRecord(name, lane, t.ident, t.name, start, end, attrs,
                         span_id or next(_IDS), parent_id)
        with self._lock:
            self._buf.append(rec)  # deque(maxlen) evicts the oldest
            self._appended += 1

    # -- inspection ----------------------------------------------------------

    def spans(self) -> List[SpanRecord]:
        """The retained spans, oldest first."""
        with self._lock:
            return list(self._buf)

    @property
    def dropped(self) -> int:
        """Spans evicted by the ring buffer since the last clear() —
        the no-silent-truncation counter."""
        with self._lock:
            return self._appended - len(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._appended = 0

    # -- export --------------------------------------------------------------

    def trace_events(self) -> List[dict]:
        """The retained spans as a Chrome trace-event list: one
        Perfetto process group per lane, one track per OS thread,
        complete ("X") events in microseconds since the tracer epoch,
        plus a visible drop-note instant when the ring buffer evicted
        anything.

        **Flow events (span links)**: a span recorded with the
        reserved attrs ``flow_id`` (one id) or ``flow_ids`` (several)
        plus ``flow_ph`` (``"s"`` start / ``"t"`` step / ``"f"`` end)
        additionally emits Chrome flow events bound to its slice
        (same ts/pid/tid; steps and ends bind to the enclosing slice
        via ``bp: "e"``). The serve layer keys these by request_id, so
        a request split across N micro-batches renders in Perfetto as
        ONE connected flow: enqueue → each dispatch → resolution. The
        reserved attrs are consumed here — they do not appear in the
        exported slice args (``request_id`` is set separately where a
        visible arg is wanted)."""
        recs = self.spans()
        dropped = self.dropped
        lanes = sorted({r.lane for r in recs})
        pid_of = {lane: i + 1 for i, lane in enumerate(lanes)}
        events: List[dict] = []
        for lane in lanes:
            events.append({"name": "process_name", "ph": "M",
                           "pid": pid_of[lane], "tid": 0,
                           "args": {"name": lane}})
        named_threads = set()
        for r in recs:
            pid = pid_of[r.lane]
            key = (pid, r.thread_id)
            if key not in named_threads:
                named_threads.add(key)
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": r.thread_id,
                               "args": {"name": r.thread_name}})
            args = dict(r.attrs, span_id=r.span_id,
                        parent_id=r.parent_id)
            flow_ph = args.pop("flow_ph", None)
            flow_ids = args.pop("flow_ids", None)
            flow_id = args.pop("flow_id", None)
            ts = round((r.start - self._epoch) * 1e6, 3)
            dur = round(max(r.end - r.start, 0.0) * 1e6, 3)
            events.append({
                "name": r.name, "cat": r.lane, "ph": "X",
                "ts": ts,
                "dur": dur,
                "pid": pid, "tid": r.thread_id,
                "args": args,
            })
            if flow_ph in ("s", "t", "f"):
                ids = (list(flow_ids) if flow_ids
                       else [flow_id] if flow_id is not None else [])
                # flow events of one id must be in timestamp order:
                # starts/steps stamp at their slice's start, the END
                # stamps at its slice's end — the request span opens
                # at submit time, so an end at its start would precede
                # the enqueue start and break the chain
                fts = ts + dur if flow_ph == "f" else ts
                for fid in ids:
                    flow = {"name": "request", "cat": "request_flow",
                            "ph": flow_ph, "id": str(fid), "ts": fts,
                            "pid": pid, "tid": r.thread_id}
                    if flow_ph != "s":
                        # bind to the enclosing slice, not the next
                        # one to start (Chrome trace-format contract)
                        flow["bp"] = "e"
                    events.append(flow)
        if dropped:
            events.append({
                "name": f"ring buffer dropped {dropped} oldest spans "
                        f"(capacity {self.capacity}; raise "
                        "SPARKDL_TPU_TRACE_BUFFER)",
                "ph": "i", "s": "g", "ts": 0.0, "pid": 0, "tid": 0,
                "args": {"dropped": dropped}})
        if self is _TRACER:
            # ONE merged timeline is the whole point: the process-wide
            # export additionally carries the spans pipeline worker
            # processes shipped through the cross-process telemetry
            # plane, clock-aligned onto THIS tracer's epoch, each
            # worker on its own process track (obs/remote.py; lanes
            # claim small pids, workers claim WORKER_PID_BASE+i, so
            # the two families cannot collide)
            try:
                from sparkdl_tpu.obs import remote
                events.extend(
                    remote.aggregator().trace_events(self._epoch))
            # sparkdl-lint: allow[H12] -- the parent-side trace must export even if the remote merge breaks; aggregator ingest errors are already counted (worker.ingest_errors)
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "trace export: worker-span merge failed; exporting "
                    "parent spans only")
        return events

    def export(self, path: str) -> int:
        """Write the trace-event JSON list to ``path`` (loadable in
        ui.perfetto.dev / chrome://tracing); returns the span count."""
        events = self.trace_events()
        with open(path, "w", encoding="utf-8") as f:
            json.dump(events, f, default=str)
        return sum(1 for e in events if e.get("ph") == "X")

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        del state["_buf"]          # remote-side spans stay remote
        del state["_appended"]
        del state["_local"]        # open spans belong to this process's threads
        del state["_epoch"]        # perf_counter origins are per-process
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._buf = collections.deque(maxlen=self.capacity)
        self._appended = 0
        self._local = threading.local()
        self._epoch = time.perf_counter()


_TRACER = Tracer()


def tracer() -> Tracer:
    """THE process-wide tracer every instrumented layer records into
    (one shared clock is the whole point)."""
    return _TRACER


def span(name: str, lane: str = "host", **attrs):
    """Module-level shorthand for ``tracer().span(...)`` — the form the
    instrumented hot paths use. Disarmed it returns one shared no-op
    object: no allocation, no lock."""
    t = _TRACER
    if not t.armed:
        return _NOOP
    return _Span(t, name, lane, attrs)


def timed_device_get(value):
    """THE instrumented drain: every runner funnels its
    device→host result syncs through this one call (``SlabSink.write``
    delegates here), so the stall the overlap strategies exist to hide
    shows up as a ``device_get`` span on the ``device`` lane. Returns
    ``(host_value, seconds)`` — ONE pair of clock reads feeds both the
    span and the caller's accounting (``transfer_wait_seconds``), so
    the printed and traced numbers cannot drift. The explicit transfer
    stays legal under ``SPARKDL_TPU_SANITIZE=1``'s transfer guard (the
    guard bans implicit transfers only) and is H1-allowlisted as the
    drain path's single blessed sync."""
    import jax

    t = _TRACER
    t0 = time.perf_counter()
    host = jax.device_get(value)
    end = time.perf_counter()
    if t.armed:
        t._record("device_get", "device", t0, end, {})
    return host, end - t0
