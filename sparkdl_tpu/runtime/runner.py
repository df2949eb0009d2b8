"""Per-partition batch runner.

The TPU-native replacement for TensorFrames' JNI block execution
(reference L1, ``tfs.map_rows``/``map_blocks`` → executor JVM → JNI →
libtensorflow ``Session::Run``): a partition's rows arrive as contiguous
host arrays, are cut into fixed-size device batches (XLA needs static
shapes — the last chunk is padded and its outputs truncated), dispatched
asynchronously to the accelerator, and gathered back as numpy.

The ship path (there is one; what the chip measured of the others it
replaced is in ``PERF.md`` §6, PR 30):

* a partition's rows are cut into chunks of the device batch
  (:func:`iter_padded_chunks`; the tail is padded through
  :class:`PadStaging`);
* each chunk is launched at once: JAX enqueues the jitted call and
  returns, so the host→device transfer of chunk *i+1* overlaps the
  device's compute of chunk *i* (the sharded runner places a chunk
  explicitly first where the runtime demands it: ``place``);
* results wait in a window ``max_inflight`` deep (default
  :data:`MAX_INFLIGHT_BATCHES`) and drain, oldest first, into one
  preallocated slab per output (:class:`SlabSink`). ``max_inflight=0``
  drains each result as soon as it is enqueued: no queue, flat memory;
* the window can outlive a ``run()``: a caller that knows its next
  inputs announces them (``run(inputs, upcoming=...)``; the engine
  does, one block ahead), their first chunks are dispatched as this
  run's last results drain, and the next ``run()`` starts with them at
  the head of its queue instead of uploading into an idle device
  (:class:`BoundaryCarry` has the contract). Nothing announced, nothing
  carried; a window of depth 0 has nothing in flight to carry under.

``max_inflight`` is read afresh at every ``run()``.

Copy discipline (inside a partition one upload hides under each device
step, ``PERF.md`` §5: every byte the host copies on the ship side
comes out of that margin):

* outputs land in ONE preallocated ``[N, *out_shape]`` slab per name —
  each drained batch writes its row range in place, so there is no
  per-batch list append and no final full-output ``np.concatenate``
  (which re-copied the entire output after the last batch, serialized
  behind all device work).
* inputs chunk as plain views when the leading-dim slice is already
  contiguous (no per-chunk ``ascontiguousarray`` copy); only the padded
  tail — and non-contiguous rows — are staged, through ONE persistent
  per-runner buffer reused across calls instead of a fresh
  ``np.concatenate`` allocation per tail.
* :class:`RunnerMetrics` counts ``bytes_staged`` / ``bytes_copied`` /
  ``transfer_wait_seconds`` so a run proves the copies went away
  rather than asserting it. Batch-aligned contiguous device runs
  report BOTH byte counters as exactly 0.

Host-backend ModelFunctions (ingested TF SavedModels — see
``graph/ingest.py``) run synchronously on CPU, unpadded, exactly where
the reference ran them.

The copy discipline is ENFORCED, not just measured: statically by
sparkdl-lint (``python -m sparkdl_tpu.analysis``, rule H1 — no host
sync outside the allowlisted drain path) and dynamically by
``SPARKDL_TPU_SANITIZE=1``, which arms ``jax.transfer_guard`` around
the dispatch/drain loop below (``runtime/sanitize.py``) so any
implicit device→host transfer a future refactor sneaks in raises at
the offending line instead of silently re-serializing the ship path.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from sparkdl_tpu.autotune.core import poll as autotune_poll
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry, span, timed_device_get
from sparkdl_tpu.obs.compile_log import compile_log
from sparkdl_tpu.obs.ledger import ledger_poll
from sparkdl_tpu.obs.watchdog import pulse as watchdog_pulse
from sparkdl_tpu.obs.watchdog import watch as watchdog_watch
from sparkdl_tpu.resilience.faults import maybe_fail
from sparkdl_tpu.runtime.sanitize import ship_guard

# Depth of the in-flight window: device batches enqueued before the
# oldest result is fetched. 2 = classic double-buffering (one executing,
# one queued behind it, its upload under the first's compute), which
# bounds device memory and how stale the oldest enqueued buffer can
# get. The carry's invariants are stated in it (BoundaryCarry).
MAX_INFLIGHT_BATCHES = 2


def resolve_max_inflight(max_inflight: Optional[int]) -> int:
    """Validate/default the window's depth — shared by BatchRunner and
    ShardedBatchRunner. 0 is legal: a zero-length queue, every result
    drained as soon as it is enqueued (the flat-memory setting)."""
    if max_inflight is None:
        return MAX_INFLIGHT_BATCHES
    if max_inflight < 0:
        raise ValueError(f"max_inflight must be >= 0, got {max_inflight}")
    return int(max_inflight)


# once-per-process-per-reason degrade warnings (the imageIO
# fused-fallback precedent): a long degraded stream must not re-log
# the same degrade per run; the log keeps the first occurrence per
# reason.
_WARNED_REASONS: set = set()


def warn_once(reason: str, msg: str, *args) -> None:
    """Log ``msg`` at WARNING exactly once per process per ``reason``
    key. Inside a telemetry-armed pipeline worker process the event
    ships to the parent instead (which dedupes ACROSS workers and logs
    once, :mod:`sparkdl_tpu.obs.remote`); everywhere else the hook is
    one module-global ``None`` check."""
    if reason in _WARNED_REASONS:
        return
    _WARNED_REASONS.add(reason)
    from sparkdl_tpu.obs import remote
    if remote.capture_degrade(f"runner:{reason}",
                              msg % args if args else msg):
        return
    logging.getLogger(__name__).warning(msg, *args)


def check_row_counts(inputs: Dict[str, np.ndarray]) -> int:
    """Validate equal leading dims across named inputs; returns N."""
    names = list(inputs)
    if not names:
        raise ValueError("no inputs")
    n = len(inputs[names[0]])
    for k, v in inputs.items():
        if len(v) != n:
            raise ValueError(f"input {k!r} has {len(v)} rows, expected {n}")
    return n


def check_against_signature(inputs: Dict[str, np.ndarray],
                            model_fn: ModelFunction) -> None:
    """Every declared model input must be present with the declared
    per-row shape — checked here, where both names are known, instead
    of surfacing as a bare KeyError or a flax shape error from deep
    inside the traced program. Extra keys are tolerated (the model
    ignores them). Unknowns skip the shape check: None dims, and the
    empty shape () on HOST-backend models, where ingested TF graphs
    use it as the unknown-rank sentinel (graph/ingest.py) — on jax
    models () genuinely means scalar rows and IS enforced."""
    sig = model_fn.input_signature
    missing = [k for k in sig if k not in inputs]
    if missing:
        raise ValueError(
            f"model {model_fn.name!r} inputs {missing} missing from "
            f"runner inputs {sorted(inputs)}")
    for k, (shape, _dtype) in sig.items():
        if any(d is None for d in shape):
            continue
        if shape == () and model_fn.backend != "jax":
            continue
        got = tuple(np.shape(inputs[k])[1:])
        if got != tuple(shape):
            raise ValueError(
                f"input {k!r} rows have shape {got}; model "
                f"{model_fn.name!r} expects {tuple(shape)}")


class PadStaging:
    """Persistent per-runner staging buffers for the padded tail chunk.

    The tail is the only chunk that cannot ship as a plain view (XLA
    needs the static chunk shape); it is written into ONE buffer per
    input name, reused across ``run()`` calls, replacing the fresh
    ``np.concatenate`` allocation every tail previously paid. Reuse is
    safe because a runner drains every result of its OWN inputs before
    ``run()`` returns, and the tail is staged at most once per call —
    the buffer is never rewritten while a batch that may alias it (CPU
    backends zero-copy numpy inputs) is still in flight. (Chunks
    dispatched for the NEXT run stay in flight past the return; they
    stage through a buffer of their own, :class:`BoundaryCarry`.) Byte
    counters
    accumulate per call into :class:`CopyCounters` so
    :class:`RunnerMetrics` can prove what was and wasn't copied.
    """

    def __init__(self):
        self._bufs: Dict[str, np.ndarray] = {}

    def stage(self, name: str, rows: np.ndarray, chunk_size: int,
              counters: Optional["CopyCounters"] = None) -> np.ndarray:
        """Copy ``rows`` into the persistent ``[chunk_size, *row]``
        buffer for ``name``, zero the pad region, return the buffer."""
        shape = (chunk_size,) + rows.shape[1:]
        with span("pad_stage", lane="ship", rows=len(rows),
                  input=name):
            buf = self._bufs.get(name)
            if buf is None or buf.shape != shape \
                    or buf.dtype != rows.dtype:
                buf = np.zeros(shape, rows.dtype)
                self._bufs[name] = buf
            valid = len(rows)
            buf[:valid] = rows
            # the buffer is reused: rows beyond this call's valid count
            # may hold a previous tail's data and must be re-zeroed
            if valid < chunk_size:
                buf[valid:] = 0
        if counters is not None:
            counters.bytes_staged += rows.nbytes
            if not rows.flags.c_contiguous:
                counters.bytes_copied += rows.nbytes
        return buf

    def stage_parts(self, name: str, parts: List[np.ndarray],
                    chunk_size: int,
                    counters: Optional["CopyCounters"] = None
                    ) -> np.ndarray:
        """Write several row arrays into CONSECUTIVE ranges of the
        persistent ``[chunk_size, *row]`` buffer for ``name``, zero the
        pad tail, return the buffer — the serve layer's multi-request
        coalesce analogue of :meth:`stage` (one request = one part).
        The same reuse-safety argument applies: the caller must fully
        drain the dispatched batch before staging the next one (the
        server's dispatcher does — ``runner.run`` returns drained)."""
        if not parts:
            raise ValueError("stage_parts needs at least one part")
        total = sum(len(p) for p in parts)
        if total > chunk_size:
            raise ValueError(
                f"parts hold {total} rows > chunk_size {chunk_size}")
        shape = (chunk_size,) + parts[0].shape[1:]
        with span("pad_stage", lane="ship", rows=total, input=name,
                  parts=len(parts)):
            buf = self._bufs.get(name)
            if buf is None or buf.shape != shape \
                    or buf.dtype != parts[0].dtype:
                buf = np.zeros(shape, parts[0].dtype)
                self._bufs[name] = buf
            lo = 0
            for rows in parts:
                buf[lo:lo + len(rows)] = rows
                lo += len(rows)
            if lo < chunk_size:
                buf[lo:] = 0
        if counters is not None:
            for rows in parts:
                counters.bytes_staged += rows.nbytes
                if not rows.flags.c_contiguous:
                    counters.bytes_copied += rows.nbytes
        return buf


@dataclass
class ChunkPhases:
    """Per-run phase timestamps on the dispatched chunks, accumulated
    by :func:`dispatch_chunks` when a caller hands one in (``None`` —
    the default — costs a single ``is not None`` check per chunk).

    The serve layer's per-request timelines (obs/request_log.py) use
    this to subdivide a request's ``device`` phase into what the ship
    state machine actually did with it: host→device placement
    (``device_put_s``), jitted-call enqueue (``enqueue_s`` — on async
    backends the enqueue, not compute), and the drain wait
    (``drain_s``, the same clock reads as ``transfer_wait_seconds``).
    Plain data, no lock: one accumulator belongs to one run() call."""

    device_put_s: float = 0.0
    enqueue_s: float = 0.0
    drain_s: float = 0.0


@dataclass
class CopyCounters:
    """Per-call host-copy accounting, folded into RunnerMetrics.

    ``bytes_staged``: tail-chunk rows written through the persistent
    pad-staging buffer (zero when N is a multiple of the chunk size).
    ``bytes_copied``: input bytes copied to make a chunk contiguous
    (non-contiguous sources, e.g. broadcast hyperparameter columns) —
    exactly 0 for batch-aligned contiguous inputs: those ship as plain
    views with no host-side staging copy at all."""

    bytes_staged: int = 0
    bytes_copied: int = 0


def iter_padded_chunks(inputs: Dict[str, np.ndarray], n: int,
                       chunk_size: int,
                       staging: Optional[PadStaging] = None,
                       counters: Optional[CopyCounters] = None,
                       start: int = 0
                       ) -> Iterator[Tuple[int, Dict[str, np.ndarray]]]:
    """Cut [N, ...] host arrays into contiguous fixed-size chunks
    (XLA needs static shapes); the tail is zero-padded. Yields
    ``(n_valid, chunk)`` — callers truncate outputs to ``n_valid``.
    ``start`` (a multiple of ``chunk_size``, or ``n``) skips the rows
    a carried window already dispatched (:class:`BoundaryCarry`).

    Full chunks whose leading-dim slice is already contiguous are
    yielded as plain VIEWS — zero host copies; non-contiguous rows are
    copied (counted in ``counters.bytes_copied``). The tail stages
    through ``staging`` (one persistent buffer per input, reused across
    calls) instead of a fresh concatenate-allocated copy."""
    if staging is None:
        staging = PadStaging()
    for lo in range(start, n, chunk_size):
        hi = min(lo + chunk_size, n)
        chunk = {}
        for k, v in inputs.items():
            rows = v[lo:hi]
            if hi - lo < chunk_size:
                chunk[k] = staging.stage(k, rows, chunk_size, counters)
            elif rows.flags.c_contiguous:
                chunk[k] = rows  # zero-copy view
            else:
                # a fresh copy per full chunk, NOT the shared staging
                # buffer: several full chunks are in flight at once
                # under async dispatch, and CPU backends may alias the
                # numpy buffer zero-copy — a reused buffer would be
                # rewritten under an unconsumed batch
                chunk[k] = np.ascontiguousarray(rows)
                if counters is not None:
                    counters.bytes_copied += rows.nbytes
        yield hi - lo, chunk


class SlabSink:
    """Preallocated ``[N, *out_shape]`` outputs, written in place.

    Each drained batch writes ``res[k][:valid]`` directly into its row
    range — no per-batch list append, no final full-output
    ``np.concatenate`` (which re-copied the entire output in one
    serialized pass after all device work finished). Slabs allocate
    lazily from the first drained batch's shapes/dtypes, so the sink
    needs no model signature and works for host backends too.
    ``transfer_wait`` accumulates time blocked in ``device_get`` — the
    ship-side stall the in-flight window exists to hide (on the chip:
    the host waiting for a busy device, the healthy state)."""

    def __init__(self, n: int):
        self.n = n
        self.transfer_wait = 0.0
        self._row = 0
        self._slabs: Optional[Dict[str, np.ndarray]] = None

    def write(self, valid: int, res) -> None:
        # the ONE blessed device→host sync (obs/trace.py — spanned on
        # the "device" lane and H1-allowlisted there); the span and
        # this counter share the same clock reads
        host, wait = timed_device_get(res)
        self.transfer_wait += wait
        if self._slabs is None:
            self._slabs = {
                k: np.empty((self.n,) + np.shape(v)[1:],
                            np.asarray(v).dtype)
                for k, v in host.items()}
        lo = self._row
        for k, v in host.items():
            self._slabs[k][lo:lo + valid] = np.asarray(v)[:valid]
        self._row = lo + valid

    def result(self) -> Dict[str, np.ndarray]:
        assert self._row == self.n and self._slabs is not None, \
            (self._row, self.n)
        return self._slabs


def drain_bounded(pending: "collections.deque", sink: SlabSink,
                  limit: int):
    """device_get completed batches into the output slab until at most
    ``limit`` remain enqueued (the backpressure half of async
    dispatch)."""
    while len(pending) > limit:
        # fault-injection site (resilience/faults.py): the result
        # drain — a device error mid-device_get is the realistic
        # failure. The batch stays queued: a retried run()
        # re-dispatches from its own inputs, never from this queue.
        maybe_fail("ship.drain")
        sink.write(*pending.popleft())


def checkout_staging(staging: PadStaging, lock: threading.Lock
                     ) -> Tuple[PadStaging, bool]:
    """(stager, locked): the persistent stager when uncontended, else a
    private throwaway — concurrent run() calls on one runner must not
    race on the shared pad buffers; release the lock iff ``locked``."""
    if lock.acquire(blocking=False):
        return staging, True
    return PadStaging(), False


def inputs_identity(inputs: Dict[str, np.ndarray]) -> Optional[tuple]:
    """Which memory a dict of host arrays views: per name the buffer
    address, shape, dtype and strides. Two dicts cut from the same
    Arrow buffers compare equal though they share no array object (a
    caller may make a block's tensors once to announce it and again
    to run it). None where an input is not an ndarray: nothing to
    compare, so such inputs are never carried."""
    ident = []
    for k in sorted(inputs):
        v = inputs[k]
        if not isinstance(v, np.ndarray):
            return None
        ident.append((k, v.__array_interface__["data"][0], v.shape,
                      v.dtype.str, v.strides))
    return tuple(ident)


@dataclass
class _Carried:
    """What one ``run()`` left in flight for the next: the results of
    the announced inputs' first chunks, in row order. ``inputs`` keeps
    the announced memory alive, so an address in ``identity`` cannot
    come to mean other rows while the carry exists."""

    identity: tuple
    inputs: Dict[str, np.ndarray]
    batch_size: int
    owner: int
    results: "collections.deque"


class CarryWindow:
    """One ``run()``'s hold on its runner's :class:`BoundaryCarry`,
    handed to :func:`dispatch_chunks`: ``head`` is the run's own first
    results where the run before already launched them (the head of
    its pending queue), ``next_chunks()`` announces the next run's
    inputs (asked once, when this run's own chunks are all dispatched),
    and ``launched`` collects what is dispatched from them. ``began``
    says how the run started: ``"carried"``, ``"cold"`` (it followed
    another run of the runner and found nothing), or None (the
    runner's first run)."""

    def __init__(self, head: "collections.deque", began: Optional[str],
                 announce=None):
        self.head = head
        self.began = began
        self.launched: collections.deque = collections.deque()
        self.leaves: Optional[_Carried] = None
        self._announce = announce

    @property
    def rows_in_flight(self) -> int:
        return sum(valid for valid, _ in self.head)

    def next_chunks(self):
        announce, self._announce = self._announce, None
        return announce(self) if announce is not None else None


class BoundaryCarry:
    """A runner's in-flight window between two ``run()`` calls.

    Inside one ``run()`` the device always holds the next chunk while
    it computes this one; at the end of the inputs the window used to
    drain to nothing, and the next ``run()`` uploaded its first chunk
    into an idle device. A caller that knows its next inputs hands
    them to ``run(inputs, upcoming=...)``; when the run's own chunks
    are all dispatched, :func:`dispatch_chunks` goes on dispatching
    from the upcoming inputs, one chunk per drained one, and the run
    returns with up to ``max_inflight`` of them in flight, owned here.

    Contract:

    * **Taken over** only by a ``run()`` of the thread that left the
      carry, whose inputs view the announced memory
      (:func:`inputs_identity`) and which cuts at the same batch
      size. Its results become the head of that run's pending queue
      and land in its own slab, in order.
    * **Dropped** (``ship.carry_dropped``) when that thread's next
      ``run()`` brings other inputs or another batch size, when the
      ``run()`` that launched it raises (a retry re-dispatches from
      its own inputs), or by :meth:`drop` (the engine calls it when a
      stream ends or is abandoned). Dropping only forgets the results:
      nothing waits for them.
    * **Bypassed** by a ``run()`` that overlaps another on the same
      runner (``uncontended`` false: the staging try-lock was taken)
      and by one that finds another thread's carry: it runs cold and
      announces nothing. Another thread's carry stays where it is;
      the thread's own, launched for this very run, is dropped.

    ``ship.boundary_carried`` / ``ship.boundary_cold`` count how runs
    began (:class:`CarryWindow`). One small lock guards the slot; the
    carried results are only ever touched by their owner's thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._held: Optional[_Carried] = None
        self._ran = False

    # in flight means on THIS process's device: a runner shipped in a
    # stage closure (spark_binding) arrives with an empty window
    def __reduce__(self):
        return (BoundaryCarry, ())

    @contextlib.contextmanager
    def window(self, inputs: Dict[str, np.ndarray], upcoming,
               batch_size: int, model_fn: ModelFunction,
               counters: "CopyCounters", uncontended: bool):
        """One ``run()``'s :class:`CarryWindow`, around its dispatch:
        on entry take over or drop what the run before left and
        prepare the announcement of ``upcoming`` (the next run's
        inputs, or a callable giving them or None); on a clean exit
        keep what the run launched for the next, on an exception
        forget it. Entered and left under the runner's staging
        try-lock (``uncontended`` says whether it was won)."""
        me = threading.get_ident()
        with self._lock:
            followed, self._ran = self._ran, True
            held = self._held
            if held is not None and held.owner != me:
                held, mine = None, False    # another thread's: stays
            else:
                # this thread's own carry was launched for THIS run:
                # taken over now or never
                self._held, mine = None, uncontended
        head: collections.deque = collections.deque()
        if held is not None:
            if mine and held.batch_size == batch_size \
                    and held.identity == inputs_identity(inputs):
                head = held.results
            else:
                default_registry().counter("ship.carry_dropped").add()
        began = "carried" if head else "cold" if followed else None
        if began == "carried":
            default_registry().counter("ship.boundary_carried").add()
        elif began == "cold":
            default_registry().counter("ship.boundary_cold").add()

        def announce(window: CarryWindow):
            nxt = upcoming() if callable(upcoming) else upcoming
            identity = inputs_identity(nxt) if nxt else None
            if identity is None:
                return None
            try:
                n = check_row_counts(nxt)
                check_against_signature(nxt, model_fn)
            except ValueError:
                return None     # the run they belong to raises it
            window.leaves = _Carried(identity, nxt, batch_size, me,
                                     window.launched)
            # a stager of their own: the runner's persistent tail
            # buffer may still be aliased by this run's last chunk
            return iter_padded_chunks(nxt, n, batch_size, PadStaging(),
                                      counters)

        window = CarryWindow(
            head, began,
            announce if mine and upcoming is not None else None)
        try:
            yield window
        except BaseException:
            if window.launched:
                window.launched.clear()
                default_registry().counter("ship.carry_dropped").add()
            raise
        if window.launched:
            # the slot is empty: only an uncontended run announces, it
            # emptied the slot above, and no other can fill it before
            # the runner's staging lock is released
            with self._lock:
                self._held = window.leaves

    def drop(self) -> None:
        """Forget a carry this thread left (no-op without one)."""
        me = threading.get_ident()
        with self._lock:
            held = self._held
            if held is None or held.owner != me:
                return
            self._held = None
        default_registry().counter("ship.carry_dropped").add()
        default_registry().gauge("ship.inflight").set(0)

    @property
    def in_flight(self) -> int:
        """Device batches held between runs (0: nothing pending)."""
        with self._lock:
            held = self._held
        return len(held.results) if held is not None else 0


def dispatch_chunks(fn, params, chunks, max_inflight: int,
                    sink: SlabSink, place=None,
                    phases: Optional[ChunkPhases] = None,
                    carry: Optional[CarryWindow] = None) -> int:
    """THE dispatch loop, shared by BatchRunner._run_device and
    ShardedBatchRunner.run: launch each chunk, keep at most
    ``max_inflight`` results in flight behind it, drain the oldest
    into ``sink``; then, where a caller announced the next inputs, go
    on into them. Returns the number of batches of this run's inputs.

    ``carry`` (optional :class:`CarryWindow`; :class:`BoundaryCarry`
    has the contract) lets the in-flight window outlive the call.
    ``carry.head`` starts the pending queue: results of ``chunks``'
    predecessors that the run before launched, drained into ``sink``
    first, in order. When ``chunks`` runs dry with results still in
    flight, ``carry.next_chunks()`` is asked once for the next run's
    chunks; each is dispatched as one of this run's results drains (an
    ordinary ``dispatch`` span with ``whose="next"``), so no more than
    ``max_inflight + 1`` batches are ever in flight and one upload
    starts per step, as inside a run. Their results go to
    ``carry.launched`` and are never drained here: this run returns
    when its own are in the slab. The caller owns ``carry`` before and
    after (an exception leaves ``carry.launched`` for it to forget).
    With ``max_inflight=0`` nothing is in flight when ``chunks`` runs
    dry, so nothing is carried. Without ``carry`` the call dispatches
    and drains and leaves nothing behind.

    ``place`` (optional) explicitly device_puts a chunk at dispatch —
    the sharded runner's multi-process requirement. ``phases``
    (optional) accumulates per-chunk placement/enqueue timestamps for
    the serve layer's request timelines (:class:`ChunkPhases`); the
    drain half is the sink's ``transfer_wait``, folded in by the
    caller."""
    pending: collections.deque = (carry.head if carry is not None
                                  else collections.deque())
    batches = len(pending)
    reg = default_registry()
    # queue-depth gauges, process-global: ship.inflight is the LAST
    # observed depth (concurrent runners overwrite each other — per-run
    # depth over time lives in the armed trace's dispatch/device_get
    # spans), ship.inflight_peak the process-LIFETIME high-water mark
    depth = reg.gauge("ship.inflight")
    depth_peak = reg.gauge("ship.inflight_peak")
    # stall-watchdog activity: one source per dispatching thread
    # (concurrent runners must not mask each other's wedge); a beat per
    # chunk, so a dispatch/drain that stops advancing past the
    # threshold trips the stall verdict
    wd_source = f"ship.dispatch@{threading.get_ident()}"

    def launch(valid, chunk, into, **whose):
        """Place (where the caller must) and enqueue one chunk; its
        result joins ``into``."""
        watchdog_pulse(wd_source)
        # fault-injection site: one chunk's input-side placement/
        # dispatch (disarmed: one armed-check)
        maybe_fail("ship.device_put")
        if place is not None:
            put_t0 = time.perf_counter() if phases is not None else 0.0
            with span("device_put", lane="ship", rows=valid):
                chunk = place(chunk)
            if phases is not None:
                phases.device_put_s += time.perf_counter() - put_t0
        # NOTE: on async backends this span times the ENQUEUE of
        # the jitted call, not device compute — device-side time is
        # only host-observable at the drain (the device_get span)
        enq_t0 = time.perf_counter() if phases is not None else 0.0
        with span("dispatch", lane="ship", rows=valid, **whose):
            res = fn(params, chunk)
        if phases is not None:
            phases.enqueue_s += time.perf_counter() - enq_t0
        into.append((valid, res))

    with watchdog_watch(wd_source):
        for valid, chunk in chunks:
            launch(valid, chunk, pending)
            batches += 1
            depth.set(len(pending))
            depth_peak.set_max(len(pending))
            drain_bounded(pending, sink, max_inflight)
            depth.set(len(pending))
        upcoming = None
        if carry is not None and pending:
            upcoming = carry.next_chunks()
        while upcoming is not None and pending:
            # the window goes on into the next run's inputs: one
            # chunk of theirs in for each result of ours out
            nxt = next(upcoming, None)
            if nxt is None:
                break
            launch(nxt[0], nxt[1], carry.launched, whose="next")
            inflight = len(pending) + len(carry.launched)
            depth.set(inflight)
            depth_peak.set_max(inflight)
            drain_bounded(pending, sink,
                          max(0, max_inflight - len(carry.launched)))
        drain_bounded(pending, sink, 0)
        depth.set(len(carry.launched) if carry is not None else 0)
    return batches


def record_run_feeds(model_fn: ModelFunction,
                     inputs: Dict[str, np.ndarray],
                     elapsed_s: float, wait_s: float,
                     batches: int = 0,
                     flops_per_batch: Optional[float] = None) -> None:
    """Feed the utilization ledger's compute/link lanes
    (obs/ledger.py) from one completed ``run()``: dispatch+drain wall
    as device-run busy time, the drain waits as link-wait time, and —
    device backends only (host models ship nothing) — the input bytes
    handed to device dispatch. When the compile log recorded the
    program's ``cost_analysis()`` FLOPs (obs/compile_log.py), the
    executed FLOPs also accumulate — the ledger's compute lane then
    divides by a model-specific ceiling instead of a generic busy
    fraction (``compute_basis`` names which). Monotonic counters,
    shared by BatchRunner and ShardedBatchRunner so both runners'
    traffic lands in the same roofline."""
    reg = default_registry()
    reg.counter("device.run_seconds").add(elapsed_s)
    reg.counter("ship.transfer_wait_seconds_total").add(wait_s)
    if flops_per_batch and batches:
        reg.counter("device.flops_total").add(
            float(flops_per_batch) * batches)
    if model_fn.backend != "host":
        # getattr: array-likes without nbytes (exotic duck-typed
        # inputs) ship unknown bytes — an under-count, never a crash
        reg.counter("ship.bytes_shipped").add(
            sum(int(getattr(v, "nbytes", 0)) for v in inputs.values()))


@dataclass
class RunnerMetrics:
    """Throughput + host-copy counters (SURVEY §5: the reference had
    none — these exist to prove the north-star number, and to prove
    the ship-path copies went away rather than asserting it).

    ``bytes_staged``: input bytes written through the reusable
    pad-staging buffer (tail chunks only). ``bytes_copied``: input
    bytes copied to make chunks contiguous — exactly 0 for
    batch-aligned contiguous device runs, the zero-copy hot path.
    ``transfer_wait_seconds``: time blocked in ``device_get`` drains
    (the benchmark's ``runner.transfer_wait_share``)."""

    rows: int = 0
    batches: int = 0
    seconds: float = 0.0
    bytes_staged: int = 0
    bytes_copied: int = 0
    transfer_wait_seconds: float = 0.0
    # how device runs began (BoundaryCarry): with their first chunks
    # already in flight, or with none though another run came before
    boundary_carried: int = 0
    boundary_cold: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    # sparkdl-lint H3 contract: one metrics object is shared by
    # concurrent run() calls (the concurrent-transform safety test
    # drives four threads through one runner) — every write to these
    # counters must hold self._lock, and the analyzer checks it.
    _lock_guards = ("rows", "batches", "seconds", "bytes_staged",
                    "bytes_copied", "transfer_wait_seconds",
                    "boundary_carried", "boundary_cold")

    def add(self, rows: int, batches: int, seconds: float,
            bytes_staged: int = 0, bytes_copied: int = 0,
            transfer_wait_seconds: float = 0.0,
            began: Optional[str] = None):
        """``began``: :attr:`CarryWindow.began` of a device run."""
        with self._lock:
            self.rows += rows
            self.batches += batches
            self.seconds += seconds
            self.bytes_staged += bytes_staged
            self.bytes_copied += bytes_copied
            self.transfer_wait_seconds += transfer_wait_seconds
            self.boundary_carried += int(began == "carried")
            self.boundary_cold += int(began == "cold")

    # Locks don't pickle; stage closures holding a metrics object must
    # ship to Spark executors (spark_binding), so the lock is dropped on
    # the wire and recreated on arrival. NOTE the boundary this implies:
    # each task increments its own deserialized copy and discards it —
    # the driver-side object stays at zero on SparkEngine runs. That is
    # deliberate (aggregating counters back through the Arrow stream is
    # not the engine contract); on a cluster, read Spark's own task
    # metrics/UI. Driver-side metrics are a LocalEngine feature.
    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    @property
    def rows_per_second(self) -> float:
        with self._lock:
            return self.rows / self.seconds if self.seconds else 0.0

    def publish(self, registry) -> None:
        """Set this runner's cumulative counters as ``ship.*`` gauges
        in an :class:`~sparkdl_tpu.obs.registry.MetricsRegistry` —
        idempotent (gauges, not counter adds), so reports can publish
        on every render without double counting."""
        with self._lock:
            vals = {"ship.rows": self.rows,
                    "ship.batches": self.batches,
                    "ship.seconds": self.seconds,
                    "ship.bytes_staged": self.bytes_staged,
                    "ship.bytes_copied": self.bytes_copied,
                    "ship.transfer_wait_seconds":
                        self.transfer_wait_seconds}
        for name, value in vals.items():
            registry.gauge(name).set(value)


class BatchRunner:
    """Runs a ModelFunction over host arrays in fixed-size device chunks."""

    # run() accepts the phases= accumulator (ChunkPhases) — the serve
    # layer probes this instead of the signature so prebuilt custom
    # runners without it keep working
    supports_phases = True

    def __init__(self, model_fn: ModelFunction, batch_size: int = 64,
                 metrics: Optional[RunnerMetrics] = None,
                 max_inflight: Optional[int] = None):
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        self.model_fn = model_fn
        self.batch_size = batch_size
        self.metrics = metrics or RunnerMetrics()
        # the in-flight window's depth (0 = a zero-length queue)
        self.max_inflight = resolve_max_inflight(max_inflight)
        # persistent pad staging, reused across run() calls; checked
        # out under a try-lock so concurrent run() calls on one runner
        # fall back to a private throwaway stager instead of racing
        self._staging = PadStaging()
        self._staging_lock = threading.Lock()
        # the in-flight window between two run() calls
        self._carry = BoundaryCarry()

    def _checkout_staging(self) -> Tuple[PadStaging, bool]:
        return checkout_staging(self._staging, self._staging_lock)

    def drop_carry(self) -> None:
        """Forget device batches a ``run(..., upcoming=...)`` of this
        thread left in flight for a run that will not come (the engine
        calls it when a stream ends or is abandoned)."""
        self._carry.drop()

    # Locks (and warm staging buffers) don't pickle; device stage
    # closures holding a runner ship to Spark executors
    # (spark_binding) — same discipline as RunnerMetrics.
    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_staging", None)
        state.pop("_staging_lock", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._staging = PadStaging()
        self._staging_lock = threading.Lock()

    @property
    def preferred_chunk(self) -> int:
        """Row count at which run() pads nothing: the device batch.
        Device stages publish this as their plan batch_hint so the
        engine can feed batch-aligned blocks across partitions."""
        return self.batch_size

    def _chunks(self, n: int, batch_size: int):
        for lo in range(0, n, batch_size):
            yield lo, min(lo + batch_size, n)

    def warmup(self) -> bool:
        """Pre-trace/compile the jitted program at the device batch
        shape (one zeros run of ``preferred_chunk`` rows) so the first
        real ``run()`` pays no compile; no-op (False) for host
        backends. See :func:`warmup_runner`."""
        return warmup_runner(self)

    def run(self, inputs: Dict[str, np.ndarray],
            phases: Optional[ChunkPhases] = None,
            upcoming=None) -> Dict[str, np.ndarray]:
        """inputs: {name: [N, *row_shape]} → {name: [N, *out_shape]}.
        ``phases`` (optional :class:`ChunkPhases`) accumulates this
        run's placement/enqueue/drain timestamps for per-request
        attribution (the serve layer's timelines).

        ``upcoming`` (optional) announces the NEXT call's inputs: a
        dict like ``inputs``, or a callable giving one (or None) that
        is asked once, when this call's own chunks are all dispatched.
        Their first chunks are then dispatched under this call's last
        steps and stay in flight when it returns, owned by the runner
        (:class:`BoundaryCarry`: taken over by the next ``run()`` of
        this thread if its inputs view the announced memory and the
        batch size has not moved; dropped otherwise, on an exception
        here, and by :meth:`drop_carry`). Without ``upcoming`` nothing
        is left in flight and the call dispatches and drains as it
        always did."""
        n = check_row_counts(inputs)
        if n == 0:
            # BEFORE the signature check: empty variable-list columns
            # arrive flat — (0,) — and stages must tolerate empty
            # batches (the schema-probe contract)
            return self._empty_outputs()
        check_against_signature(inputs, self.model_fn)

        # the span opens where ``t0`` is read and closes where
        # ``elapsed`` is, so it times the interval RunnerMetrics.seconds
        # does; everything the run causes on this thread (pad_stage,
        # device_put, dispatch, device_get, compile) is its descendant
        with span("runner.run", lane="ship", rows=n):
            t0 = time.perf_counter()
            counters = CopyCounters()
            # ONE snapshot per run: a live controller
            # (sparkdl_tpu/autotune) may move batch_size from another
            # thread between runs — every read below must see the same
            # value or a mid-run shrink would cut chunks on a stale
            # stride and skip rows
            batch_size = self.batch_size
            flops = None
            began = None
            if self.model_fn.backend == "host":
                out, wait = self._run_host(inputs, n, batch_size)
            else:
                out, wait, began = self._run_device(
                    inputs, n, counters, batch_size, phases, upcoming)
                # the compiled program's FLOPs, when the compile log
                # recorded them (obs/compile_log.py) — the ledger's
                # model-specific compute feed. Armed-gated: a disarmed
                # run's dispatches refresh nothing, so a stale number
                # from an earlier armed phase must not be credited
                if compile_log().armed:
                    flops = getattr(self.model_fn.jitted(),
                                    "last_flops", None)
            batches = -(-n // batch_size)
            elapsed = time.perf_counter() - t0
        self.metrics.add(n, batches, elapsed,
                         bytes_staged=counters.bytes_staged,
                         bytes_copied=counters.bytes_copied,
                         transfer_wait_seconds=wait, began=began)
        record_run_feeds(self.model_fn, inputs, elapsed, wait,
                         batches=batches, flops_per_batch=flops)
        # the autotune controller's apply point: knobs only ever move
        # BETWEEN runs, on the thread that just finished one (a single
        # armed-check when the controller is disarmed)
        autotune_poll()
        ledger_poll()
        return out

    # -- host path ----------------------------------------------------------

    def _run_host(self, inputs, n, batch_size
                  ) -> Tuple[Dict[str, np.ndarray], float]:
        # slab outputs here too: each chunk's result writes its row
        # range of one preallocated [N, *out] array (lazily shaped from
        # the first chunk), replacing the per-chunk list + final concat
        slabs: Optional[Dict[str, np.ndarray]] = None
        for lo, hi in self._chunks(n, batch_size):
            chunk = {k: v[lo:hi] for k, v in inputs.items()}
            out = self.model_fn.apply_fn(self.model_fn.params, chunk)
            if slabs is None:
                slabs = {k: np.empty((n,) + np.shape(v)[1:],
                                     np.asarray(v).dtype)
                         for k, v in out.items()}
            for k, v in out.items():
                slabs[k][lo:hi] = np.asarray(v)
        assert slabs is not None
        return slabs, 0.0

    # -- device path --------------------------------------------------------

    def _run_device(self, inputs, n, counters: CopyCounters,
                    batch_size: int,
                    phases: Optional[ChunkPhases] = None,
                    upcoming=None
                    ) -> Tuple[Dict[str, np.ndarray], float,
                               Optional[str]]:
        fn = self.model_fn.jitted()
        params = self.model_fn.device_params()
        # enqueue then drain to self.max_inflight: 0 = drain at once,
        # >0 = bounded async dispatch (module docstring)
        sink = SlabSink(n)
        staging, locked = self._checkout_staging()
        try:
            # the window the run before left for these inputs becomes
            # the head of this run's; a run that overlaps another
            # bypasses it. SPARKDL_TPU_SANITIZE=1: transfer_guard
            # turns any implicit device→host sync inside
            # dispatch/drain into an error (the sink's explicit
            # device_get stays legal)
            with self._carry.window(inputs, upcoming, batch_size,
                                    self.model_fn, counters,
                                    uncontended=locked) as carry, \
                    ship_guard():
                chunks = iter_padded_chunks(inputs, n, batch_size,
                                            staging, counters,
                                            start=carry.rows_in_flight)
                dispatch_chunks(fn, params, chunks, self.max_inflight,
                                sink, phases=phases, carry=carry)
        finally:
            if locked:
                self._staging_lock.release()
        if phases is not None:
            # the drain half: the same clock reads as
            # transfer_wait_seconds (timed_device_get), so the traced
            # and attributed numbers cannot drift
            phases.drain_s += sink.transfer_wait
        return sink.result(), sink.transfer_wait, carry.began

    def _empty_outputs(self) -> Dict[str, np.ndarray]:
        if self.model_fn.backend != "jax":
            # Host fns (TF SavedModels) usually handle N=0; running them
            # is the only way to learn the per-row output shape so empty
            # partitions keep the same schema as full ones. A model that
            # rejects N=0 must fail loudly here — a guessed fallback
            # schema would diverge from non-empty partitions and break
            # far away at the Arrow concat.
            try:
                zero = {
                    k: np.zeros(
                        (0,) + tuple(d if d is not None else 1
                                     for d in shape), dtype)
                    for k, (shape, dtype)
                    in self.model_fn.input_signature.items()
                }
                return {k: np.asarray(v)
                        for k, v in self.model_fn.apply_fn(
                            self.model_fn.params, zero).items()}
            except Exception as e:
                raise ValueError(
                    f"host model {self.model_fn.name!r} failed on the "
                    "empty (N=0) probe batch used to determine the "
                    "empty-partition output schema; filter out empty "
                    "partitions or make the model accept N=0") from e
        return empty_jax_outputs(self.model_fn)


def empty_jax_outputs(model_fn: ModelFunction) -> Dict[str, np.ndarray]:
    """Schema-correct zero-row outputs for a jax-backend ModelFunction
    (shared by BatchRunner and ShardedBatchRunner)."""
    sig = model_fn.output_signature()
    return {k: np.zeros((0,) + tuple(shape), dtype)
            for k, (shape, dtype) in sig.items()}


def warmup_runner(runner) -> bool:
    """Pre-trace + compile ``runner``'s jitted program at its device
    batch shape by running one zeros batch of ``preferred_chunk`` rows
    — so the FIRST real request never pays the jit trace/compile
    (the serve layer's warmup contract, docs/SERVING.md; shared by
    BatchRunner.warmup and ShardedBatchRunner.warmup).

    Every runner dispatch uses exactly one device shape (chunks are
    padded to ``preferred_chunk``), so one zeros run covers it. Returns
    False without running for host backends (no jit to warm) and for
    signatures with unknown (None) dims, where no concrete warmup batch
    exists.

    A successful warmup marks the model's compiled programs STEADY in
    the process-wide compile log (obs/compile_log.py): from here on
    any real compile through them counts
    ``compile.unexpected_retraces`` — the no-first-request-pays-compile
    guarantee enforced at runtime, not just pinned by trace-count
    tests."""
    model_fn = runner.model_fn
    if model_fn.backend != "jax":
        return False
    sig = model_fn.input_signature
    if any(d is None for shape, _ in sig.values() for d in shape):
        logging.getLogger(__name__).debug(
            "warmup skipped for %s: unknown dims in signature",
            model_fn.name)
        return False
    n = runner.preferred_chunk
    zeros = {k: np.zeros((n,) + tuple(shape), dtype)
             for k, (shape, dtype) in sig.items()}
    runner.run(zeros)
    compile_log().mark_model_steady(model_fn, reason="warmup_runner")
    return True
