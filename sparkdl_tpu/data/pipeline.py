"""Parallel host pipeline: overlapped multi-worker decode/ship with a
shared-memory Arrow hand-off and an ordered bounded re-merge.

BENCH r05's standing note says it plainly: on a 1-core host decode and
ship-side host work serialize on one Python stream
(``1/decode + 1/ship ~= 1/pipeline``) while the compute ceiling sits
~6x higher. ROADMAP item 3 names the fix — the tf.data shape
(PAPERS.md, arxiv 2101.12127): run the host-side transformation
(source load + decode stages) on N workers concurrently with bounded
read-ahead and ORDERED delivery, so decode overlaps ship/dispatch
instead of taking turns with it.

This module is that worker pool. :class:`LocalEngine` selects it per
``execute()`` when its ``pipeline_workers`` knob (ctor arg or
``SPARKDL_TPU_PIPELINE_WORKERS``, typo-degrades to serial) resolves to
>= 2:

* **process pool** (the default for CPU-heavy Python decode, which the
  GIL would otherwise serialize): each partition's source load + host
  stage prefix runs in a worker process; the finished Arrow fragment
  is handed back through a POSIX shared-memory segment carrying the
  Arrow IPC stream — the consumer copies the segment ONCE into
  process-owned bytes (a single bounded memcpy, counted in
  ``pipeline.handoff_bytes``) and maps the record batch zero-copy over
  them, so fragment rows flow into the engine's existing zero-copy
  re-chunk / ``PadStaging`` ship path without any further per-row
  work. Fragments under :data:`SHM_MIN_BYTES` skip the segment and
  ride the result pipe directly (the segment costs two syscalls; tiny
  metadata batches don't earn them).
* **thread pool fallback** where the process pool cannot apply — a
  plan or source that does not survive the cloudpickle round-trip (the
  sparkdl-lint H3 shipping discipline: locks/pools must drop on the
  wire), or a platform without a usable start method. Counted in
  ``pipeline.fallbacks``, never silent. Thread workers overlap only
  where stages release the GIL (the native libjpeg decode shim does;
  pure-PIL decode does not — exactly the case the process pool
  exists for).
* **ordered bounded re-merge**: workers complete in any order; results
  park in a reorder window bounded by the ``read_ahead`` knob
  (``SPARKDL_TPU_PIPELINE_READ_AHEAD``) and are yielded strictly in
  partition order — row identity and order are EXACT through the
  pooled path, including under mid-stream ``LiveBatchHint`` changes
  (the re-chunk cut downstream re-reads its hint between blocks
  exactly as in the serial path; pinned in tests/test_pipeline.py).

Degrades (each counted, none silent): requested workers < 2, a
config typo, or a 1-core host in auto mode run SERIAL — the existing
single-stream path, byte-for-byte. An explicit ``pipeline_mode``
("process"/"thread") trusts the caller and skips the core check (the
CI correctness drills run pooled on 1-core hosts on purpose).

Failure semantics match the engine's: a worker raising surfaces ONE
typed error to the consumer (process-mode exceptions are cloudpickled
back and re-raised; a worker that cannot even report yields
:class:`PipelineWorkerError`); transient failures re-run through the
engine's shared :class:`~sparkdl_tpu.resilience.policy.RetryPolicy`
(parent-side re-submit — the budget only bounds amplification if every
retry shares the bucket); on error or early abandonment in-flight
siblings are cancelled, EFFECTFUL plans/sources drain before control
returns (the engine's quiesce discipline), and any completed-but-
unconsumed shared-memory segment is released so an abandoned stream
cannot leak ``/dev/shm``.

Observability: every in-flight partition feeds the stall watchdog
(source ``pipeline.decode:<index>`` — a wedged worker fires a stall
NAMING the partition and recovers when it completes); merged fragments
land on the tracer's ``engine`` lane as ``pipeline.fragment`` spans;
the registry carries ``pipeline.*`` gauges/counters
(docs/OBSERVABILITY.md); and :func:`state` renders the live
worker/read-ahead/mode picture for ``/statusz`` and flight bundles.
Worker-process host busy time is
reported back per task and folded into ``engine.busy_seconds`` by the
consumer, so the utilization ledger's decode lane keeps its ONE feed —
and gains a per-worker ceiling basis: with N pooled workers the lane's
ceiling is N busy-seconds per wall second (``decode_basis:
"busy/pooled-workers"``, obs/ledger.py).
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time
import uuid
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import pyarrow as pa

from sparkdl_tpu.obs import default_registry, span
from sparkdl_tpu.obs.watchdog import watchdog
from sparkdl_tpu.resilience.errors import TransientError

logger = logging.getLogger(__name__)

#: worker-count env knob; 0/unset/typo = serial (the existing path)
ENV_WORKERS = "SPARKDL_TPU_PIPELINE_WORKERS"
#: reorder-window env knob; default 2x workers (enough look-ahead to
#: keep every worker busy while the consumer drains in order)
ENV_READ_AHEAD = "SPARKDL_TPU_PIPELINE_READ_AHEAD"
#: pool-mode env knob: auto (process, thread fallback) | process | thread
ENV_MODE = "SPARKDL_TPU_PIPELINE_MODE"
#: multiprocessing start-method override (auto: spawn where the main
#: module supports re-import, else fork)
ENV_MPCTX = "SPARKDL_TPU_PIPELINE_MPCTX"

_MODES = ("auto", "process", "thread")

#: fragments smaller than this ride the result pipe instead of a
#: shared-memory segment (two syscalls + an mmap don't pay for tiny
#: metadata batches; decoded image fragments clear this easily)
SHM_MIN_BYTES = 64 * 1024


def _count(what: str, amount: float = 1.0) -> None:
    default_registry().counter(f"pipeline.{what}").add(amount)


def resolve_workers(explicit: Optional[int]) -> int:
    """The requested worker count: an explicit ctor value wins, then
    :data:`ENV_WORKERS`. A typo or negative value degrades to 0
    (serial) with one warning + ``pipeline.config_errors`` — the
    ledger/env-parsing precedent: a config typo must never make the
    engine unusable."""
    if explicit is not None:
        return max(0, int(explicit))
    raw = os.environ.get(ENV_WORKERS, "")
    if not raw:
        return 0
    try:
        val = int(raw)
        if val < 0:
            raise ValueError(val)
        return val
    except ValueError:
        logger.warning("%s=%r is not a non-negative int; running the "
                       "serial host path", ENV_WORKERS, raw)
        _count("config_errors")
        return 0


def resolve_read_ahead(explicit: Optional[int], workers: int) -> int:
    """The reorder-window depth (in-flight partitions ahead of the
    merge point): explicit wins, then :data:`ENV_READ_AHEAD`, then
    2x workers — the same typo-degrade contract as
    :func:`resolve_workers`."""
    default = max(2, 2 * max(1, workers))
    if explicit is not None:
        return max(1, int(explicit))
    raw = os.environ.get(ENV_READ_AHEAD, "")
    if not raw:
        return default
    try:
        val = int(raw)
        if val < 1:
            raise ValueError(val)
        return val
    except ValueError:
        logger.warning("%s=%r is not a positive int; using the default "
                       "%d", ENV_READ_AHEAD, raw, default)
        _count("config_errors")
        return default


def resolve_mode(explicit: Optional[str]) -> str:
    """Pool mode: explicit wins, then :data:`ENV_MODE`, then auto."""
    raw = explicit or os.environ.get(ENV_MODE, "") or "auto"
    raw = raw.lower()
    if raw not in _MODES:
        logger.warning("pipeline mode %r is not one of %s; using "
                       "'auto'", raw, _MODES)
        _count("config_errors")
        return "auto"
    return raw


_warned_once: set = set()
_warn_lock = threading.Lock()


def _warn_once(key: str, msg: str, *args) -> None:
    with _warn_lock:
        fire = key not in _warned_once
        _warned_once.add(key)
    if fire:
        # inside a telemetry-armed worker process the degrade event
        # ships to the parent (which dedupes ACROSS workers and logs
        # once); everywhere else this is one module-global None check
        from sparkdl_tpu.obs import remote
        if remote.capture_degrade(f"pipeline:{key}",
                                  msg % args if args else msg):
            return
        logger.warning(msg, *args)


def effective_workers(requested: int, mode: str,
                      record: bool = True) -> int:
    """The worker count a pooled stream actually runs: 0 (serial) when
    fewer than 2 are requested, and — in auto mode only — on a 1-core
    host, where overlapping decode with itself buys nothing and the
    pool's hand-off overhead would eat the 5%-of-serial degrade budget.
    An explicit process/thread mode trusts the caller (correctness
    drills run pooled on 1-core CI hosts on purpose). Degrades count
    ``pipeline.degrade_events`` — but only when a stream is actually
    being resolved: informational callers (bench labeling a result,
    the sweep labeling a grid row) pass ``record=False`` so the
    documented "every downgrade counted" contract stays a count of
    downgrades, not of questions."""
    req = max(0, int(requested))
    if req < 2:
        return 0
    if mode == "auto" and (os.cpu_count() or 1) < 2:
        if record:
            _warn_once("1core",
                       "pipeline: %d workers requested on a 1-core "
                       "host; running the serial host path (explicit "
                       "pipeline_mode forces the pool)", req)
            _count("degrade_events")
        return 0
    return req


def _spawn_safe() -> bool:
    """Whether the ``spawn`` start method can re-import ``__main__``
    here: real script files and ``python -m`` runs qualify; ``python -``
    heredocs and REPLs do not (spawn would die trying to re-run
    ``<stdin>``)."""
    main = sys.modules.get("__main__")
    if main is None:
        return False
    if getattr(main, "__spec__", None) is not None:
        return True
    path = getattr(main, "__file__", None)
    return bool(path) and os.path.exists(str(path))


def _mp_context():
    """The start method for worker processes: the env override when
    valid, else ``spawn`` where the main module survives re-import
    (fresh children — no inherited jax/OpenMP thread state), else
    ``fork`` (the only method that works under ``python -`` heredocs;
    children must stay off jax, which these workers do — they run
    Arrow/PIL/native decode only). None = no process pool here."""
    import multiprocessing as mp
    avail = mp.get_all_start_methods()
    raw = os.environ.get(ENV_MPCTX, "")
    if raw:
        if raw in avail:
            return mp.get_context(raw)
        logger.warning("%s=%r is not one of %s; auto-selecting",
                       ENV_MPCTX, raw, avail)
        _count("config_errors")
    if "spawn" in avail and _spawn_safe():
        return mp.get_context("spawn")
    if "fork" in avail:
        return mp.get_context("fork")
    return None


class PipelineWorkerError(RuntimeError):
    """A pooled worker failed in a way that could not be reported as
    its original typed exception (the exception itself did not survive
    the wire). Carries the worker-side repr so the failure still names
    itself."""


class PipelineHandoffError(TransientError):
    """The shared-memory hand-off of a finished fragment failed on the
    consumer side (segment missing/unreadable) — distinct from the
    worker failing, and TYPED transient (resilience/errors.py) so the
    parent-side retry actually fires: a re-run re-creates the
    segment."""


# ---------------------------------------------------------------------------
# worker side (runs in the pool process; must not touch jax)
# ---------------------------------------------------------------------------

#: per-worker-process plan cache, keyed by stream token — tasks carry
#: the cloudpickled plan redundantly (any task can land on any worker)
#: but each worker deserializes a stream's plan once. Bounded at a few
#: entries with oldest-out eviction so CONCURRENT streams sharing the
#: pool don't thrash each other's entry (a clear-on-miss single slot
#: would re-deserialize per task exactly when two streams interleave)
#: while a parade of finished streams still can't pin dead plans.
_PLAN_CACHE: "OrderedDict[str, list]" = OrderedDict()
_PLAN_CACHE_MAX = 4


def _encode_batch(batch: pa.RecordBatch) -> pa.Buffer:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, batch.schema) as writer:
        writer.write_batch(batch)
    return sink.getvalue()


def _decode_batch(data) -> pa.RecordBatch:
    """Arrow IPC stream bytes -> the fragment batch, zero-copy over
    ``data`` (the py_buffer keeps the owning bytes alive for as long
    as any downstream slice of the batch does)."""
    reader = pa.ipc.open_stream(pa.py_buffer(data))
    batch = reader.read_next_batch()
    return batch


def _with_frame(result: tuple, agent) -> tuple:
    """Append the telemetry frame to a task result tuple — ONLY when a
    worker agent is armed, so the disarmed hand-off carries zero extra
    bytes and keeps its exact pre-telemetry tuple shapes (the parent
    demuxes by the per-kind base length, ``_split_frame``)."""
    if agent is None:
        return result
    try:
        return result + (agent.cut_frame(),)
    except Exception:
        # telemetry must never fail the fragment it rides with
        logger.exception("pipeline worker: telemetry frame cut failed; "
                         "fragment ships without it")
        return result


def _pooled_partition_task(token: str, plan_blob: bytes,
                           src_blob: bytes, index: int,
                           shm_min: int,
                           tel: Optional[dict] = None) -> tuple:
    """One partition's source load + host-stage prefix, in a worker
    process. Returns a plain-picklable result tuple (never raises —
    exceptions ship back cloudpickled so their type survives):

    ``("shm", name, nbytes, busy_s, timings, rows)`` — fragment in a
    shared-memory segment the CONSUMER owns from here on (this side
    unregisters it from its resource tracker before returning);
    ``("buf", payload_bytes, busy_s, timings, rows)`` — small fragment
    riding the result pipe;
    ``("err", exc_blob_or_None, repr, type_name)`` — the failure,
    typed where cloudpickle can carry it.

    ``tel`` is the parent's telemetry config
    (:func:`sparkdl_tpu.obs.remote.telemetry_config`): when set, this
    process's :class:`~sparkdl_tpu.obs.remote.TelemetryAgent` arms
    (once — pool workers persist) and every result tuple gains ONE
    trailing frame element carrying the worker's spans, counter
    deltas, watchdog verdict, degrade events, and fault state back to
    the parent aggregator. ``None`` (disarmed) leaves the tuples
    byte-identical to the pre-telemetry shapes.
    """
    import cloudpickle
    agent = None
    try:
        if tel is not None:
            try:
                from sparkdl_tpu.obs import remote as _remote
                agent = _remote.worker_agent(tel)
            except Exception:
                # the fragment matters more than its telemetry
                logger.exception("pipeline worker: telemetry agent "
                                 "arming failed; task runs unobserved")
                agent = None
        plan = _PLAN_CACHE.get(token)
        if plan is None:
            plan = cloudpickle.loads(plan_blob)
            _PLAN_CACHE[token] = plan
            while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
                _PLAN_CACHE.popitem(last=False)
        else:
            _PLAN_CACHE.move_to_end(token)
        source = cloudpickle.loads(src_blob)
        logical = getattr(source, "logical_index", None)
        if logical is not None:
            index = logical
        # the engine's fault-injection sites apply to pooled partitions
        # too (env-armed config reaches the worker — and the telemetry
        # plane ships programmatic specs, so with an armed agent the
        # per-site counters recorded here reach the parent as
        # worker.<i>.faults.* instead of dying with the process)
        from sparkdl_tpu.resilience.faults import maybe_fail
        try:
            maybe_fail("pipeline.worker_death")
        except BaseException:
            # the ROADMAP-named worker-death drill: a REAL corpse (the
            # parent sees BrokenProcessPool, exactly like an OOM
            # kill), not a reportable error shipped back politely
            os._exit(1)
        from sparkdl_tpu.obs.watchdog import watchdog as _watchdog
        wd = _watchdog()
        busy = 0.0
        timings: List[Tuple[str, float, int]] = []
        with wd.watch("pipeline.worker_decode"), \
                span("worker.decode", lane="worker", partition=index):
            maybe_fail("pipeline.worker_decode")
            maybe_fail("engine.source_load")
            t0 = time.perf_counter()
            with span("worker.source_load", lane="worker",
                      partition=index):
                batch = source.load()
            busy += time.perf_counter() - t0
            for stage in plan:
                wd.pulse("pipeline.worker_decode")
                maybe_fail("engine.stage_apply")
                rows_in = batch.num_rows
                t0 = time.perf_counter()
                with span(f"worker.stage:{stage.name}", lane="worker",
                          partition=index, rows=rows_in):
                    batch = (stage.fn(batch, index) if stage.with_index
                             else stage.fn(batch))
                dt = time.perf_counter() - t0
                busy += dt
                timings.append((stage.name, dt, rows_in))
        payload = _encode_batch(batch)
        rows = batch.num_rows
        if agent is not None:
            # worker-side row accounting for report --workers / the
            # flight bundle's workers[] counter snapshot; parent-side
            # mirror lands as worker.<i>.pipeline.worker_rows
            _count("worker_rows", rows)
        if payload.size >= shm_min:
            try:
                from multiprocessing import shared_memory
                shm = shared_memory.SharedMemory(create=True,
                                                 size=payload.size)
            except Exception as e:
                # platforms without /dev/shm (or a full one) fall back
                # to the pipe — the fragment still arrives
                logger.warning("pipeline: shared-memory segment "
                               "unavailable (%s); fragment rides the "
                               "result pipe", e)
                shm = None
            if shm is not None:
                # cast to the flat byte view shm.buf exposes (the
                # Arrow buffer's own memoryview is not always 'B')
                shm.buf[:payload.size] = memoryview(payload).cast("B")
                name = shm.name
                try:
                    # ownership moves to the consumer: without this the
                    # worker's resource tracker unlinks the segment when
                    # the pool retires the process
                    from multiprocessing import resource_tracker
                    resource_tracker.unregister(shm._name,
                                                "shared_memory")
                except Exception as e:
                    # best-effort: double-unlink at exit is a warning,
                    # not a leak (the consumer unlinks first)
                    logger.debug("pipeline: resource-tracker "
                                 "unregister failed: %s", e)
                shm.close()
                return _with_frame(
                    ("shm", name, payload.size, busy, timings, rows),
                    agent)
        return _with_frame(
            ("buf", payload.to_pybytes(), busy, timings, rows), agent)
    except BaseException as exc:  # ships back typed; never raises
        blob = None
        try:
            exc.__traceback__ = None  # tracebacks don't pickle
            blob = cloudpickle.dumps(exc)
        except Exception:
            blob = None
        return _with_frame(
            ("err", blob, repr(exc), type(exc).__name__), agent)


# ---------------------------------------------------------------------------
# consumer side
# ---------------------------------------------------------------------------

#: base tuple length per result kind — the frame demux key: a result
#: longer than its base length carries EXACTLY one trailing telemetry
#: frame (armed streams only; disarmed tuples are the base shapes)
_RESULT_BASE_LEN = {"shm": 6, "buf": 5, "err": 4}


def _split_frame(result: tuple) -> Tuple[tuple, Optional[dict]]:
    """``(base_result, frame_or_None)`` — the parent half of the
    transport seam (:mod:`sparkdl_tpu.obs.remote`)."""
    if not isinstance(result, tuple) or not result:
        return result, None
    base = _RESULT_BASE_LEN.get(result[0])
    if base is None or len(result) <= base:
        return result, None
    return result[:base], result[base]


def _ingest_frame(frame: Optional[dict]) -> None:
    if frame is None:
        return
    try:
        from sparkdl_tpu.obs import remote
        remote.aggregator().ingest(frame)
    except Exception:
        # ingest() guards itself (worker.ingest_errors); this catches
        # an unimportable aggregator, which must not fail the fragment
        default_registry().counter("worker.ingest_errors").add()
        logger.exception("pipeline: telemetry frame ingest failed")


def _release_result(result: tuple) -> None:
    """Free a completed-but-unconsumed task result (early-stop or
    error abandonment): the shared-memory segment must be unlinked or
    an abandoned stream leaks ``/dev/shm``."""
    result, frame = _split_frame(result)
    _ingest_frame(frame)  # an abandoned fragment's telemetry survives
    if not isinstance(result, tuple) or not result or result[0] != "shm":
        return
    try:
        from multiprocessing import shared_memory
        shm = shared_memory.SharedMemory(name=result[1])
        shm.close()
        shm.unlink()
        _count("fragments_discarded")
    except FileNotFoundError:
        # already released (a racing consumer unlinked first) — the
        # goal state, but say so for a postmortem reading debug logs
        logger.debug("pipeline: abandoned fragment %r already "
                     "released", result[1])
    except Exception as e:
        _count("handoff_errors")
        logger.warning("pipeline: releasing an abandoned fragment "
                       "failed: %s", e)


def _raise_worker_error(result: tuple) -> None:
    _kind, blob, rep, type_name = result
    if blob is not None:
        import cloudpickle
        try:
            exc = cloudpickle.loads(blob)
        except Exception:
            exc = None
        if isinstance(exc, BaseException):
            raise exc
    raise PipelineWorkerError(
        f"pooled worker failed with {type_name}: {rep}")


def _consume_result(result: tuple) -> Tuple[pa.RecordBatch, float,
                                            List[tuple]]:
    """A task result tuple -> (batch, busy_seconds, stage timings).
    Shared-memory fragments are copied ONCE into process-owned bytes
    and the segment is released immediately; the batch then aliases
    the owned bytes zero-copy for the rest of its life. An armed
    stream's trailing telemetry frame is split off and ingested FIRST
    — an "err" result's frame still reaches the aggregator (the
    injected-fault drill is attributed even though the fragment
    raises)."""
    result, frame = _split_frame(result)
    _ingest_frame(frame)
    kind = result[0]
    if kind == "err":
        _raise_worker_error(result)
    if kind == "shm":
        _, name, nbytes, busy, timings, _rows = result
        from multiprocessing import shared_memory
        try:
            shm = shared_memory.SharedMemory(name=name)
        except FileNotFoundError:
            _count("handoff_errors")
            raise PipelineHandoffError(
                f"shared-memory segment {name!r} vanished before the "
                "fragment was consumed") from None
        try:
            data = bytes(shm.buf[:nbytes])  # the ONE bounded memcpy
        finally:
            shm.close()
            try:
                shm.unlink()
            except FileNotFoundError:
                logger.debug("pipeline: segment %r already unlinked",
                             name)
        _count("shm_segments")
    else:
        _, data, busy, timings, _rows = result
    _count("handoff_bytes", len(data))
    return _decode_batch(data), busy, list(timings)


# the live pooled-worker gauge the utilization ledger divides the
# decode lane by (per-worker ceiling basis): max over active streams,
# 0 when nothing pooled is running. _workers_peak additionally holds
# the max since the last consume_workers_peak() call so a ledger
# window that straddles a stream's END still divides by the workers
# that actually earned its busy seconds (an instantaneous gauge read
# at tick time would see 0 and misread a 4-worker window's 4
# busy-seconds/wall as a saturated serial lane).
_active_streams: Dict[int, Tuple[int, float]] = {}  # sid -> (workers, t0)
_active_lock = threading.Lock()
_stream_seq = 0
_workers_peak = 0
_workers_alltime = 0


def _enter_stream(workers: int) -> int:
    global _stream_seq, _workers_peak, _workers_alltime
    with _active_lock:
        _stream_seq += 1
        sid = _stream_seq
        _active_streams[sid] = (workers, time.perf_counter())
        live = max(w for w, _ in _active_streams.values())
        _workers_peak = max(_workers_peak, live)
        _workers_alltime = max(_workers_alltime, live)
    default_registry().gauge("pipeline.workers").set(live)
    return sid


def consume_workers_peak() -> int:
    """Max pooled workers live since the previous call (the ledger's
    per-window read, obs/ledger.py): covers streams that started AND
    finished inside the window. Resets the peak to the current live
    count, so each window consumes exactly its own history."""
    global _workers_peak
    with _active_lock:
        live = max((w for w, _ in _active_streams.values()), default=0)
        peak = max(_workers_peak, live)
        _workers_peak = live
        return peak


def alltime_workers_peak() -> int:
    """Process-lifetime pooled-worker high-water mark — the ledger's
    CUMULATIVE-verdict decode ceiling (a process that ever ran pooled
    banked pooled busy-seconds in the cumulative totals; dividing
    them by the serial ceiling would fabricate a saturated decode
    verdict)."""
    with _active_lock:
        live = max((w for w, _ in _active_streams.values()), default=0)
        return max(_workers_alltime, live)


def _exit_stream(sid: int) -> None:
    with _active_lock:
        entry = _active_streams.pop(sid, None)
        live = max((w for w, _ in _active_streams.values()), default=0)
    default_registry().gauge("pipeline.workers").set(live)
    if entry is not None:
        # pooled-stream ACTIVE wall seconds: PipelineTarget's
        # throughput denominator (rows per active second — idle gaps
        # between executes must not deflate a trial's evaluation)
        _count("stream_seconds", time.perf_counter() - entry[1])


# the last-resolved configuration, for /statusz and flight bundles
# (one shape everywhere)
_last_state: Dict[str, Any] = {}
_state_lock = threading.Lock()


def _record_state(**kv) -> None:
    with _state_lock:
        _last_state.update(kv)


def state() -> Dict[str, Any]:
    """The scrape-able pipeline state (``/statusz``, flight bundles):
    the last stream's resolved mode/workers/read-ahead plus the live
    ``pipeline.*`` counters."""
    snap = default_registry().snapshot()
    with _state_lock:
        out = dict(_last_state)
    with _active_lock:
        out["streams_active"] = len(_active_streams)
    out["counters"] = {k: v for k, v in snap.items()
                       if k.startswith("pipeline.")}
    return out


def _retire_worker_telemetry(handle) -> None:
    """Before a CLEAN process-pool shutdown, tell the telemetry
    aggregator these worker pids are retiring — otherwise a LATER pool
    break probes the reaped pids and misattributes the clean exits as
    deaths. Thread pools (no ``_processes``) are a no-op."""
    if handle is None:
        return
    procs = getattr(handle.pool, "_processes", None)
    if not procs:
        return
    try:
        from sparkdl_tpu.obs import remote
        remote.aggregator().note_pool_retired(list(procs.keys()))
    # sparkdl-lint: allow[H12] -- best-effort lifecycle bookkeeping: the shutdown itself proceeds either way, and an unretired slot only risks a later over-count that note_pool_broken's ERROR log surfaces
    except Exception:
        logger.exception("pipeline: worker retirement bookkeeping "
                         "failed")


class _PoolHandle:
    """One pool GENERATION. Streams pin the handle for their whole
    life (``refs``), so a live resize — the autotuner moving
    ``pipeline_workers`` while a stream is mid-flight — builds a NEW
    generation for new streams instead of shutting down (and
    cancelling the queued tasks of) the one a concurrent stream is
    still draining. A retired generation shuts down when its last
    holder releases it."""

    __slots__ = ("pool", "workers", "refs", "retired")

    def __init__(self, pool, workers: int):
        self.pool = pool
        self.workers = workers
        self.refs = 0
        self.retired = False


class HostPipeline:
    """The engine-owned worker pool + ordered re-merge
    (module docstring). One instance per :class:`LocalEngine`, built
    lazily on the first pooled ``execute()``; the pool persists across
    executes and is re-sized when the ``pipeline_workers`` knob moves
    (the autotune apply point — knob writes land between streams, the
    engine re-reads per execute; in-flight streams keep their pinned
    :class:`_PoolHandle` generation)."""

    # sparkdl-lint H3 contract: pool (re)builds can race from
    # concurrent execute() calls — pool handles and the mode
    # bookkeeping hold self._lock
    _lock_guards = ("_proc_handle", "_thread_handle", "_proc_broken")

    def __init__(self, mode: Optional[str] = None,
                 shm_min_bytes: int = SHM_MIN_BYTES):
        self.mode = resolve_mode(mode)
        self.shm_min_bytes = int(shm_min_bytes)
        self._lock = threading.Lock()
        self._proc_handle: Optional[_PoolHandle] = None
        self._proc_broken = False
        self._thread_handle: Optional[_PoolHandle] = None

    # locks and pools never ship (H3): a pipeline reachable through a
    # pickled engine arrives config-only, pools rebuilt on first use
    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        state["_proc_handle"] = None
        state["_proc_broken"] = False
        state["_thread_handle"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    # -- pools ---------------------------------------------------------------

    def _retire_locked(self, handle: Optional[_PoolHandle]
                       ) -> Optional[_PoolHandle]:
        """Mark ``handle`` retired (caller holds the lock); returns it
        when no stream still pins it — i.e. when the CALLER must shut
        it down (outside the lock)."""
        # deferred: the data layer must not pull the jax-importing
        # runtime package in at module load; retires are rare (pool
        # resize / close), so the import cost lands off the hot path
        from sparkdl_tpu.runtime.sanitize import assert_lock_owned
        assert_lock_owned(self._lock, "HostPipeline._retire_locked")
        if handle is None:
            return None
        handle.retired = True
        return handle if handle.refs <= 0 else None

    def _acquire_process(self, workers: int) -> Optional[_PoolHandle]:
        """Pin the process-pool generation at ``workers`` size for one
        stream (rebuilding when the knob moved); None when no usable
        start method exists or a previous pool broke (worker killed —
        the stream that saw it raised typed; later streams fall back
        to threads, counted by the caller)."""
        from concurrent.futures import ProcessPoolExecutor
        with self._lock:
            if self._proc_broken:
                return None
            h = self._proc_handle
            if h is not None and h.workers == workers:
                h.refs += 1
                return h
        ctx = _mp_context()
        if ctx is None:
            return None
        new = _PoolHandle(
            ProcessPoolExecutor(max_workers=workers, mp_context=ctx),
            workers)
        new.refs = 1
        shut = None
        with self._lock:
            h = self._proc_handle
            if self._proc_broken:
                shut, new = new, None      # broke while building
            elif h is not None and h.workers == workers:
                h.refs += 1                # lost a racing same-size build
                shut, new = new, h
            else:
                self._proc_handle = new
                shut = self._retire_locked(h)
        if shut is not None:
            _retire_worker_telemetry(shut)
            shut.pool.shutdown(wait=False, cancel_futures=True)
        return new

    def _acquire_thread(self, workers: int) -> _PoolHandle:
        """The thread-pool analogue of :meth:`_acquire_process`
        (always succeeds — threads need no start method)."""
        shut = None
        with self._lock:
            h = self._thread_handle
            if h is not None and h.workers == workers:
                h.refs += 1
                return h
            new = _PoolHandle(
                ThreadPoolExecutor(
                    max_workers=workers,
                    thread_name_prefix="sparkdl-pipeline"),
                workers)
            new.refs = 1
            self._thread_handle = new
            shut = self._retire_locked(h)
        if shut is not None:
            shut.pool.shutdown(wait=False, cancel_futures=True)
        return new

    def _release(self, handle: Optional[_PoolHandle]) -> None:
        """A stream is done with its pinned generation; a retired one
        shuts down when the last holder leaves (queued abandoned tasks
        cancel; running ones finish and their done-callbacks release
        any shm segments)."""
        if handle is None:
            return
        with self._lock:
            handle.refs -= 1
            shut = handle.retired and handle.refs <= 0
        if shut:
            _retire_worker_telemetry(handle)
            handle.pool.shutdown(wait=False, cancel_futures=True)

    def _mark_broken(self) -> None:
        with self._lock:
            already = self._proc_broken
            self._proc_broken = True
            shut = self._retire_locked(self._proc_handle)
            self._proc_handle = None
        if shut is not None:
            shut.pool.shutdown(wait=False, cancel_futures=True)
        if not already:
            # attribute the corpse: probe the telemetry plane's known
            # worker pids, mark the dead one, count
            # pipeline.worker_deaths, dump a flight bundle naming it
            try:
                from sparkdl_tpu.obs import remote
                remote.aggregator().note_pool_broken(
                    "process pool broke (a worker process died)")
            # sparkdl-lint: allow[H12] -- best-effort death attribution: the broken pool itself is already counted (pipeline.fallbacks) and raises typed (PipelineWorkerError) upstream
            except Exception:
                logger.exception("pipeline: worker-death attribution "
                                 "failed")

    def shutdown(self) -> None:
        with self._lock:
            handles = (self._proc_handle, self._thread_handle)
            self._proc_handle = None
            self._thread_handle = None
            for h in handles:
                if h is not None:
                    h.retired = True
        for h in handles:
            if h is not None:
                _retire_worker_telemetry(h)
                h.pool.shutdown(wait=False, cancel_futures=True)

    # -- mode resolution -----------------------------------------------------

    def _pickle_payload(self, sources: Sequence, plan: Sequence
                        ) -> Optional[Tuple[bytes, List[bytes]]]:
        """(plan blob, per-source blobs) when the H3 shipping
        discipline holds for this stream, else None — the thread
        fallback's trigger."""
        import cloudpickle
        try:
            plan_blob = cloudpickle.dumps(list(plan))
            src_blobs = [cloudpickle.dumps(s) for s in sources]
            return plan_blob, src_blobs
        except Exception as e:
            _warn_once(f"pickle:{type(e).__name__}",
                       "pipeline: plan/source does not survive the "
                       "cloudpickle round-trip (%s: %s); process pool "
                       "falls back to threads", type(e).__name__, e)
            _count("fallbacks")
            return None

    # -- the pooled stream ---------------------------------------------------

    def stream(self, sources: Sequence, plan: Sequence, engine,
               workers: int) -> Iterator[Tuple[int, pa.RecordBatch]]:
        """Yield ``(logical_index, fragment)`` in partition order with
        ``workers`` pool workers and the engine's live ``read_ahead``
        window. The generator owns its in-flight bookkeeping: early
        abandonment cancels, effectful plans/sources drain (quiesce),
        abandoned shared-memory fragments release."""
        plan = list(plan)
        mode = self.mode
        payload = None
        handle = None
        if mode in ("auto", "process"):
            payload = self._pickle_payload(sources, plan)
            if payload is not None:
                handle = self._acquire_process(workers)
            if handle is None:
                if payload is not None:
                    # pool unavailable (no start method / broken pool)
                    _warn_once("noproc",
                               "pipeline: no usable process pool on "
                               "this platform; falling back to the "
                               "thread pool")
                    _count("fallbacks")
                mode = "thread"
            else:
                mode = "process"
        read_ahead = max(1, int(getattr(engine, "pipeline_read_ahead",
                                        0) or 1))
        _record_state(mode=mode, workers=workers,
                      read_ahead=read_ahead,
                      shm_min_bytes=self.shm_min_bytes)
        default_registry().gauge("pipeline.read_ahead").set(read_ahead)
        if mode == "process":
            return self._stream_process(sources, plan, engine, workers,
                                        payload, handle)
        return self._stream_thread(sources, plan, engine, workers)

    def _stream_thread(self, sources, plan, engine, workers):
        """Thread-mode pooled stream: tasks run the engine's own
        retrying ``_run_partition`` (spans, busy-seconds feed, stage
        metrics all land exactly as in the serial path)."""
        handle = self._acquire_thread(workers)

        def submit(pos: int) -> Future:
            return handle.pool.submit(engine._run_partition,
                                      sources[pos], plan, pos)

        return self._merge(sources, plan, engine, workers, submit,
                           consume=None, resubmit=None, mode="thread",
                           handle=handle)

    def _stream_process(self, sources, plan, engine, workers, payload,
                        handle: _PoolHandle):
        plan_blob, src_blobs = payload
        token = uuid.uuid4().hex
        # resolved ONCE per stream: None (disarmed) costs nothing and
        # ships nothing; armed, every task carries the config so any
        # worker the task lands on arms its agent
        from sparkdl_tpu.obs import remote
        tel = remote.telemetry_config()

        def submit(pos: int) -> Future:
            from concurrent.futures.process import BrokenProcessPool
            try:
                return handle.pool.submit(_pooled_partition_task,
                                          token, plan_blob,
                                          src_blobs[pos], pos,
                                          self.shm_min_bytes, tel)
            except BrokenProcessPool as exc:
                self._mark_broken()
                _count("fallbacks")
                raise PipelineWorkerError(
                    "process pool broke mid-stream (a worker process "
                    "died); subsequent pooled streams fall back to "
                    "the thread pool") from exc

        def consume(pos: int, result: tuple) -> pa.RecordBatch:
            batch, busy, timings = _consume_result(result)
            # the worker's host busy time lands in the ONE decode-lane
            # feed (obs/ledger.py) — counted here because the worker's
            # own registry dies with its process
            default_registry().counter("engine.busy_seconds").add(busy)
            if engine.stage_metrics is not None:
                for name, seconds, rows in timings:
                    engine.stage_metrics.add(name, seconds, rows)
            return batch

        return self._merge(sources, plan, engine, workers, submit,
                           consume=consume, resubmit=submit,
                           mode="process", handle=handle)

    def _merge(self, sources, plan, engine, workers, submit, consume,
               resubmit, mode: str, handle: Optional[_PoolHandle]):
        """The ordered bounded re-merge (one generator, both modes).
        ``consume`` post-processes a raw future result into a batch
        (process mode: shm hand-off + accounting; thread mode: the
        result IS the batch). ``resubmit`` enables parent-side retry
        through the engine's shared RetryPolicy (process mode only —
        thread-mode tasks already retry inside ``_run_partition``).
        ``handle`` is the stream's pinned pool generation, released
        when the generator finishes/abandons."""
        drain = (any(getattr(st, "effectful", False) for st in plan)
                 or any(getattr(src, "effectful", False)
                        for src in sources))
        wd = watchdog()
        inflight = default_registry().gauge("pipeline.inflight")
        inflight_peak = default_registry().gauge(
            "pipeline.inflight_peak")

        def _logical(pos: int) -> int:
            logical = getattr(sources[pos], "logical_index", None)
            return pos if logical is None else logical

        def _wd_source(pos: int) -> str:
            return f"pipeline.decode:{_logical(pos)}"

        def _result(pos: int, fut: Future):
            try:
                raw = fut.result()
            except BaseException as exc:
                from concurrent.futures.process import BrokenProcessPool
                if isinstance(exc, BrokenProcessPool):
                    # a worker died (OOM/kill) and took the pool with
                    # it: this stream fails typed; later streams fall
                    # back to the thread pool (counted) instead of
                    # resubmitting into a corpse
                    self._mark_broken()
                    _count("fallbacks")
                    raise PipelineWorkerError(
                        "process pool broke mid-stream (a worker "
                        "process died); subsequent pooled streams "
                        "fall back to the thread pool") from exc
                raise
            if consume is None:
                return raw
            try:
                return consume(pos, raw)
            except BaseException as exc:
                if resubmit is None:
                    raise
                # parent-side re-runs through the SHARED RetryPolicy
                # (grant-by-grant, because attempt 1 — the pooled
                # task that just failed — already happened): the
                # budget only bounds sustained amplification if
                # pooled retries drain the same bucket as serial ones
                policy = engine.retry_policy
                on_retry = engine._log_retry(
                    f"pooled partition {_logical(pos)}")
                key = f"pipeline:{_logical(pos)}"
                policy.deposit()
                attempt = 1
                while True:
                    delay = policy.grant(attempt, exc, key=key)
                    if delay is None:
                        raise exc
                    on_retry(attempt, exc, delay)
                    time.sleep(delay)
                    try:
                        return consume(pos, resubmit(pos).result())
                    except BaseException as retry_exc:  # sparkdl-lint: allow[H13] -- bounded + paced by engine.retry_policy: each lap re-asks grant(), which enforces max attempts, the retry budget, and exponential backoff, and its None raises out of the loop
                        attempt += 1
                        exc = retry_exc

        def _gen():
            sid = _enter_stream(workers)
            pending: Dict[int, Future] = {}
            # one watchdog source per EXECUTING partition — begun
            # lazily once a future reports running (merely-queued
            # siblings behind a wedged worker must not fire stalls
            # mis-naming healthy partitions), ended at completion
            # (done callback) so a finished fragment parked in the
            # reorder buffer cannot read as a stall either. A worker
            # that stops making progress fires a stall NAMING its
            # partition; completion recovers it.
            watched: set = set()
            watch_lock = threading.Lock()

            def _watch(pos: int) -> None:
                with watch_lock:
                    if pos in watched:
                        return
                    watched.add(pos)
                wd.begin(_wd_source(pos))

            def _unwatch(pos: int) -> None:
                with watch_lock:
                    if pos not in watched:
                        return
                    watched.discard(pos)
                wd.end(_wd_source(pos))

            next_to_submit = 0
            next_to_yield = 0
            n = len(sources)
            try:
                while next_to_yield < n:
                    window = max(1, int(getattr(
                        engine, "pipeline_read_ahead", 0) or 1))
                    while (next_to_submit < n
                           and len(pending) < window):
                        pos = next_to_submit
                        fut = submit(pos)
                        pending[pos] = fut
                        fut.add_done_callback(
                            lambda _f, p=pos: _unwatch(p))
                        next_to_submit += 1
                        inflight.set(len(pending))
                        inflight_peak.set_max(len(pending))
                    for p, f in pending.items():
                        if f.running():
                            _watch(p)
                    pos = next_to_yield
                    fut = pending.pop(pos)
                    # we block on it next, so it counts as executing
                    # even if the running() snapshot above missed it
                    if not fut.done():
                        _watch(pos)
                    try:
                        with span("pipeline.fragment", lane="engine",
                                  partition=_logical(pos), mode=mode,
                                  workers=workers):
                            batch = _result(pos, fut)
                    finally:
                        _unwatch(pos)
                        inflight.set(len(pending))
                    _count("tasks")
                    _count("rows", batch.num_rows)
                    yield _logical(pos), batch
                    next_to_yield += 1
            finally:
                for pos, fut in pending.items():
                    if not fut.cancel():
                        # running (or already done): release any
                        # completed fragment's shm segment — an
                        # abandoned stream must not leak /dev/shm
                        if consume is not None:
                            fut.add_done_callback(self._on_abandoned)
                    _unwatch(pos)
                if drain:
                    # QUIESCE (the engine's discipline): an effectful
                    # straggler finishing AFTER the caller's cleanup
                    # ran corrupts the cleanup's outcome
                    for fut in pending.values():
                        if not fut.cancelled():
                            try:
                                fut.result()
                            except Exception as drain_err:
                                # the primary error is already
                                # propagating; record the secondary
                                logger.debug(
                                    "pipeline quiesce drain error: %s",
                                    drain_err)
                inflight.set(0)
                _exit_stream(sid)
                self._release(handle)

        return _gen()

    @staticmethod
    def _on_abandoned(fut: Future) -> None:
        try:
            result = fut.result()
        except BaseException as e:
            logger.debug("pipeline: abandoned task failed: %s", e)
            return
        _release_result(result)

    def describe(self) -> Dict[str, Any]:
        with self._lock:
            ph, th = self._proc_handle, self._thread_handle
            return {"mode": self.mode,
                    "process_pool_workers":
                        ph.workers if ph is not None else 0,
                    "process_pool_broken": self._proc_broken,
                    "thread_pool_workers":
                        th.workers if th is not None else 0,
                    "shm_min_bytes": self.shm_min_bytes}
