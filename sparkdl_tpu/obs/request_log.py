"""Per-request observability: request ids, phase timelines, and a
bounded request log.

The serving layer coalesces many requests into one micro-batch and
splits oversized requests across several (serve/batching.py), so the
lane/batch spans and the aggregate latency reservoir cannot answer the
question production debugging actually asks: *where did THIS slow
request spend its time?* This module is the request-scoped half of the
obs layer:

* every ``ModelServer.submit`` mints a ``request_id`` and (armed) a
  :class:`RequestTimeline` that rides the request through admission →
  queue wait → coalesce → staging → device run(s) → reassembly;
* completed timelines flatten to :class:`RequestRecord` — an
  end-to-end latency plus a phase breakdown whose durations sum to the
  total (the coalesce phase is the remainder: everything between the
  first take and resolution that is not staging/device/reassembly work,
  which for the single-threaded dispatcher is exactly the wait) —
  retained in THE process-wide bounded :class:`RequestLog` ring;
* armed alongside the tracer, each record also lands as a ``request``
  span on the ``request`` lane carrying the breakdown in its args, and
  the serve spans gain Perfetto flow events keyed by the request_id —
  a split request renders as ONE connected flow across its
  micro-batches, and ``python -m sparkdl_tpu.obs report --tails``
  attributes the p99 across the named phases from the exported trace.

Arming: ``SPARKDL_TPU_REQUEST_LOG=1``, ``request_log().arm()``, or —
the common case — arming the tracer (``SPARKDL_TPU_TRACE=1``): an
armed timeline without spans to link to answers half the question, so
the request log FOLLOWS the tracer unless explicitly pinned. Disarmed,
:meth:`RequestLog.timeline` returns ``None`` after one armed-check —
the tracer's shared no-op regime, pinned <10µs/submit alongside the
span bound (``tests/test_request_obs.py``).

Cardinality discipline: request ids live in records, exemplars, and
span args — NEVER in registry metric names (sparkdl-lint rule H6 bans
per-request metric names; an unbounded key set is how a metrics
backend dies). The ring is hard-bounded (``capacity`` ctor arg,
default ``SPARKDL_TPU_REQUEST_LOG_CAPACITY`` or 1024 records);
evictions count in :attr:`RequestLog.dropped` AND the registry's
``obs.request_log.dropped`` counter — never a silent truncation.

Pickle discipline (the ``StageMetrics`` precedent): the lock and the
ring drop on the wire — records are process-local forensics, like the
tracer's spans; armed-ness and capacity travel.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Dict, List, Optional

from sparkdl_tpu.obs.registry import default_registry
from sparkdl_tpu.obs.trace import tracer

_TRUE = ("1", "true", "yes", "on")

#: ring capacity (records) when SPARKDL_TPU_REQUEST_LOG_CAPACITY is unset
DEFAULT_CAPACITY = 1024

#: the named phases every record attributes its latency across —
#: ``report --tails`` and the exemplar tests key on these
PHASES = ("queue", "coalesce", "staging", "device", "reassembly")

# request ids are process-unique AND cross-process distinguishable
# (flight bundles from several processes can land in one directory).
# The pid is read per mint, NOT captured at import: a fork-started
# worker inherits this module (and a copy of the counter) — its own
# pid is what keeps its ids distinct from the parent's.
_RID_SEQ = itertools.count(1)


def _mint_rid() -> str:
    return f"r{os.getpid():x}-{next(_RID_SEQ):06x}"


def _env_armed() -> bool:
    return os.environ.get("SPARKDL_TPU_REQUEST_LOG", "").lower() in _TRUE


def _env_capacity() -> int:
    raw = os.environ.get("SPARKDL_TPU_REQUEST_LOG_CAPACITY", "")
    try:
        cap = int(raw) if raw else DEFAULT_CAPACITY
        if cap <= 0:
            raise ValueError(cap)
    except ValueError:
        # the module-level singleton parses this at import time — a
        # config typo degrades to the default, never an import error
        import logging
        logging.getLogger(__name__).warning(
            "SPARKDL_TPU_REQUEST_LOG_CAPACITY=%r is not a positive "
            "int; using the default %d", raw, DEFAULT_CAPACITY)
        cap = DEFAULT_CAPACITY
    return cap


RequestRecord = collections.namedtuple(
    "RequestRecord",
    ["request_id", "model", "rows", "batches", "status", "total_s",
     "phases", "device_detail"])


class RequestTimeline:
    """One request's phase marks, mutated only by threads that already
    serialize on the request's path (the submitting thread before
    enqueue, then the session's single dispatcher — creation
    happens-before every later mark via the queue lock), so no lock of
    its own."""

    __slots__ = ("rid", "model", "rows", "submitted", "first_taken",
                 "staging_s", "device_s", "reassembly_s", "batches",
                 "device_put_s", "enqueue_s", "drain_s")

    def __init__(self, rid: str, model: str, rows: int,
                 submitted: float):
        self.rid = rid
        self.model = model
        self.rows = rows
        self.submitted = submitted
        self.first_taken: Optional[float] = None
        self.staging_s = 0.0
        self.device_s = 0.0
        self.reassembly_s = 0.0
        self.batches = 0
        # optional device-phase detail (ChunkPhases, runtime/runner.py)
        self.device_put_s = 0.0
        self.enqueue_s = 0.0
        self.drain_s = 0.0

    def mark_taken(self, now: float) -> None:
        """First rows placed into a micro-batch — the queue phase ends
        here (idempotent: a split request is taken several times)."""
        if self.first_taken is None:
            self.first_taken = now

    def add_batch(self, staging_s: float, device_s: float,
                  detail=None) -> None:
        """One micro-batch carrying (part of) this request dispatched:
        its staging + device-run time accrues to the request (a batch
        shared by M requests costs each of them its wall time — that
        IS the request's experience of it)."""
        self.batches += 1
        self.staging_s += staging_s
        self.device_s += device_s
        if detail is not None:
            self.device_put_s += detail.device_put_s
            self.enqueue_s += detail.enqueue_s
            self.drain_s += detail.drain_s

    def add_reassembly(self, seconds: float) -> None:
        self.reassembly_s += seconds

    def finish(self, now: float, status: str) -> RequestRecord:
        """Flatten to a record whose phases sum to the end-to-end
        latency: ``coalesce`` is the remainder — all time after the
        first take that is not measured staging/device/reassembly work,
        i.e. the fill wait plus (for split requests) the wait between
        micro-batches."""
        total = max(now - self.submitted, 0.0)
        queue = max((self.first_taken if self.first_taken is not None
                     else now) - self.submitted, 0.0)
        queue = min(queue, total)
        known = (queue + self.staging_s + self.device_s
                 + self.reassembly_s)
        phases = {
            "queue": queue,
            "coalesce": max(total - known, 0.0),
            "staging": self.staging_s,
            "device": self.device_s,
            "reassembly": self.reassembly_s,
        }
        detail = None
        if self.device_put_s or self.enqueue_s or self.drain_s:
            detail = {"device_put_s": self.device_put_s,
                      "enqueue_s": self.enqueue_s,
                      "drain_s": self.drain_s}
        return RequestRecord(
            request_id=self.rid, model=self.model, rows=self.rows,
            batches=self.batches, status=status, total_s=total,
            phases=phases, device_detail=detail)

    def exemplar(self, record: RequestRecord) -> Dict[str, object]:
        """The reservoir-exemplar payload for ``record``: enough to
        resolve a scraped p99 back to the request's spans in an
        exported trace (the id) and to read its breakdown without one
        (the phases)."""
        return {"request_id": record.request_id,
                "rows": record.rows,
                "batches": record.batches,
                "phases": dict(record.phases)}


class RequestLog:
    """THE bounded process-wide ring of completed request records
    (module docstring). Standalone instances exist for tests."""

    # sparkdl-lint H3 contract: the serve dispatchers of every session
    # record concurrently — ring/counter writes hold self._lock
    _lock_guards = ("appended",)

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = _env_capacity()
        if capacity <= 0:
            raise ValueError(
                f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        # None → follow env/tracer; True/False → programmatic override
        self._override: Optional[bool] = None
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=capacity)
        self.appended = 0

    # -- arming --------------------------------------------------------------

    @property
    def armed(self) -> bool:
        ov = self._override
        if ov is not None:
            return ov
        return _env_armed() or tracer().armed

    def arm(self) -> None:
        """Record timelines regardless of the env/tracer."""
        self._override = True

    def disarm(self) -> None:
        self._override = False

    def arm_from_env(self) -> None:
        """Drop the override; follow SPARKDL_TPU_REQUEST_LOG (or the
        tracer) again."""
        self._override = None

    # -- the submit-side hot path --------------------------------------------

    def timeline(self, model: str, rows: int,
                 submitted: float) -> Optional[RequestTimeline]:
        """A minted-per-request timeline, or ``None`` disarmed (the
        shared no-op regime: one armed-check, nothing allocated)."""
        if not self.armed:
            return None
        return RequestTimeline(_mint_rid(), model, rows, submitted)

    # -- recording (dispatcher side) -----------------------------------------

    def record(self, rec: RequestRecord,
               submitted: Optional[float] = None,
               flow: bool = True) -> None:
        """Retain ``rec``; evictions count (``dropped`` + the
        registry's ``obs.request_log.dropped``) — the ring is a hard
        bound, never silent truncation. Also lands the record as a
        ``request`` span (with its phase breakdown and a flow-end
        event) when the tracer is armed, so ``report --tails`` can
        attribute the p99 from an exported trace; ``submitted`` (the
        timeline's perf_counter submit instant) anchors that span —
        callers recording at resolution time may omit it. ``flow``:
        False for requests that never reached the enqueue span (the
        flow's "s" start) — dead-at-submit / precheck rejections — a
        flow END with no start would render as a dangling arrow."""
        with self._lock:
            evicting = len(self._ring) == self._ring.maxlen
            self._ring.append(rec)
            self.appended += 1
        if evicting:
            default_registry().counter("obs.request_log.dropped").add()
        trc = tracer()
        if trc.armed:
            if submitted is None:
                submitted = time.perf_counter() - rec.total_s
            attrs = {"request_id": rec.request_id,
                     "model": rec.model, "status": rec.status,
                     "rows": rec.rows, "batches": rec.batches,
                     "phases_s": {k: round(v, 6)
                                  for k, v in rec.phases.items()}}
            if flow:
                attrs.update(flow_id=rec.request_id, flow_ph="f")
            trc._record("request", "request",
                        start=submitted, end=submitted + rec.total_s,
                        attrs=attrs)

    # -- readout -------------------------------------------------------------

    def records(self) -> List[RequestRecord]:
        """The retained records, oldest first."""
        with self._lock:
            return list(self._ring)

    @property
    def dropped(self) -> int:
        """Records evicted by the bounded ring since the last clear()."""
        with self._lock:
            return self.appended - len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.appended = 0

    def status(self) -> dict:
        """The scrape-able state (flight bundles, ``/statusz``)."""
        with self._lock:
            retained = len(self._ring)
            dropped = self.appended - retained
        return {"armed": self.armed, "capacity": self.capacity,
                "retained": retained, "dropped": dropped}

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        del state["_ring"]      # records are process-local forensics
        state["appended"] = 0
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=self.capacity)


def tails_from_records(records) -> Dict[str, object]:
    """Tail attribution over RequestRecords: p50/p99 (nearest-rank,
    SUCCESSES only — the separate-population contract) plus the p99
    specimen's phase breakdown in ms and ``attributed_pct`` (how much
    of the measured p99 the named phases account for; ≥95 is the
    acceptance bar ci gates). The
    trace-level twin is ``report.tails_summary`` (same math via
    :func:`~sparkdl_tpu.obs.registry.nearest_rank`, computed from
    exported ``request`` spans instead of live records so the CLI
    works on any trace file)."""
    from sparkdl_tpu.obs.registry import nearest_rank

    ok = [r for r in records if r.status == "ok"]
    if not ok:
        return {"requests": 0, "p50_ms": None, "p99_ms": None,
                "p99_request_id": None, "p99_batches": None,
                "attributed_pct": None, "phases_ms": {}}
    totals = sorted(r.total_s for r in ok)
    p50, p99 = nearest_rank(totals, 0.5), nearest_rank(totals, 0.99)
    worst = next(r for r in ok if r.total_s == p99)
    attributed = sum(worst.phases.values())
    return {
        "requests": len(ok),
        "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(p99 * 1e3, 3),
        "p99_request_id": worst.request_id,
        "p99_batches": worst.batches,
        "attributed_pct": round(100.0 * attributed / p99, 1)
        if p99 else 0.0,
        "phases_ms": {k: round(v * 1e3, 3)
                      for k, v in worst.phases.items()},
    }


_REQUEST_LOG = RequestLog()


def request_log() -> RequestLog:
    """THE process-wide request log the serve layer records into."""
    return _REQUEST_LOG
