"""Per-partition batch runner.

The TPU-native replacement for TensorFrames' JNI block execution
(reference L1, ``tfs.map_rows``/``map_blocks`` → executor JVM → JNI →
libtensorflow ``Session::Run``): a partition's rows arrive as contiguous
host arrays, are cut into fixed-size device batches (XLA needs static
shapes — the last chunk is padded and its outputs truncated), dispatched
asynchronously to the accelerator, and gathered back as numpy.

Transfer strategy (measured, not asserted — tools/measure_transfer.py):

* ``deferred`` — async dispatch with a small bounded queue: JAX enqueues
  each jitted call and returns immediately, so host→device transfer of
  chunk *i+1* overlaps device compute of chunk *i*; completed results
  drain once the queue exceeds ``max_inflight``. The default.
* ``host_async`` — deferred dispatch PLUS ``copy_to_host_async()`` on
  each result at enqueue, so the device→host copy of chunk *i* overlaps
  compute of *i+1* and the final ``device_get`` finds the bytes already
  landed.
* ``immediate`` — drain each chunk's result synchronously as soon as it
  is enqueued. The conservative fallback: no queue, flat memory, never
  pathological.
* ``prefetch`` — everything ``host_async`` does PLUS a depth-N input
  prefetch (``prefetch_depth``, default 1): the next N chunks are
  ``jax.device_put`` while chunk *i* computes, so the jitted call
  consumes an already-resident buffer instead of transferring at
  dispatch time, and a link whose latency exceeds one chunk's compute
  can still be kept full. Depth is a bounded look-ahead queue — each
  placed chunk holds a chunk of device memory, so deeper is NOT free;
  the autotune controller (``sparkdl_tpu/autotune``) raises it only
  while drain waits dominate.

On top of any strategy, an optional device-resident **infeed ring**
(``SPARKDL_TPU_INFEED_RING`` / the ``infeed_ring`` ctor knob, K >= 2)
keeps the last K placed chunk slabs resident in device memory,
content-addressed: a chunk whose bytes already sit in a live slot
dispatches the RESIDENT slab and ships nothing (``ship.ring_hits`` /
``ship.bytes_resident``); a chunk that must ship while every slot is
recently useful streams through with its input buffers DONATED into
the jitted call (``ModelFunction.jitted(donate_inputs=True)``) so its
HBM is reused for the outputs instead of double-buffering
(``ship.ring_donations``; probe-and-degrade to undonated dispatch on
backends whose donation is a no-op — ``ship.ring_degrade_events``).
Re-shipping bytes that crossed the link before is counted in
``ship.bytes_reshipped`` and must read 0 on a steady repeated-corpus
pass (tools/ci.sh gates it). On multi-device hosts
``SPARKDL_TPU_TRANSFER_INTERLEAVE`` / ``transfer_interleave`` >= 2
issues the per-device ``device_put`` legs of a sharded placement
concurrently instead of FIFO behind one stream
(:func:`interleaved_device_put`), bounded by the prefetch look-ahead.

Under ``deferred`` and ``host_async`` the in-flight window can also
outlive a ``run()``: a caller that knows its next inputs announces
them (``run(inputs, upcoming=...)``; the engine does, one block
ahead), their first chunks are dispatched as this run's last results
drain, and the next ``run()`` starts with them at the head of its
queue instead of uploading into an idle device
(:class:`BoundaryCarry` has the contract). Nothing announced, nothing
carried: the call dispatches and drains as it always did.

Override the default with
``SPARKDL_TPU_RUNNER_STRATEGY=immediate|deferred|host_async|prefetch``
or the ``strategy`` ctor arg; the prefetch look-ahead depth with
``SPARKDL_TPU_PREFETCH_DEPTH`` or the ``prefetch_depth`` ctor arg.
``strategy``/``max_inflight``/``prefetch_depth`` are read afresh at
every ``run()`` — a live controller (``sparkdl_tpu/autotune``) may
move them between runs without touching compiled shapes.

Copy discipline (BENCH r05: the pipeline is link-bound and on a 1-core
host every ship-side byte the host copies comes straight out of
pipeline throughput):

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
  ``transfer_wait_seconds`` so the bench proves the copies went away
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
import hashlib
import logging
import os
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from sparkdl_tpu.autotune.core import poll as autotune_poll
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry, span, timed_device_get
from sparkdl_tpu.obs.compile_log import compile_log
from sparkdl_tpu.obs.ledger import ledger_poll
from sparkdl_tpu.obs.watchdog import pulse as watchdog_pulse
from sparkdl_tpu.obs.watchdog import watch as watchdog_watch
from sparkdl_tpu.resilience.faults import maybe_fail
from sparkdl_tpu.runtime.sanitize import assert_lock_owned, ship_guard

# In-flight device batches before the oldest result is fetched, for the
# "deferred" strategy. 2 = classic double-buffering (one executing, one
# queued behind it): measured equal to deeper queues where transfers
# overlap at all (CPU: immediate 6.1 vs deferred 6.2 img/s — compute
# bound either way), while bounding device memory and capping how stale
# the oldest enqueued buffer can get.
MAX_INFLIGHT_BATCHES = 2
# host_async keeps a deeper queue: its entries' device→host copies are
# already in flight, so draining old entries is cheap, and more overlap
# helps on high-latency links (the strategy's whole point). prefetch is
# host_async plus input-side overlap and shares the depth.
MAX_INFLIGHT_HOST_ASYNC = 8
# default input look-ahead for the "prefetch" strategy: 1 is the
# PR-1 measured shape (place chunk i+1 while i computes); deeper
# look-ahead holds more chunk-sized device buffers and is the
# autotune controller's call, not a static default
DEFAULT_PREFETCH_DEPTH = 1
# device-resident infeed ring depth: 0 = off (every chunk ships).
# Once engaged the floor is K=2 — classic double-buffering is the
# smallest shape that can hold one slab resident while another lands —
# so 1 clamps up loudly. The autotune controller deepens it only while
# the utilization ledger says the pipeline is link-bound.
DEFAULT_INFEED_RING = 0
# per-device transfer interleave width: 0 = serial FIFO placement
# behind one stream (the pre-ring behavior, and all a single-device
# host can do); >= 2 issues that many per-device device_put legs of a
# sharded placement concurrently (interleaved_device_put).
DEFAULT_TRANSFER_INTERLEAVE = 0

_STRATEGIES = ("immediate", "deferred", "host_async", "prefetch")


def _default_strategy() -> str:
    env = os.environ.get("SPARKDL_TPU_RUNNER_STRATEGY")
    if env:
        if env not in _STRATEGIES:
            raise ValueError(
                f"SPARKDL_TPU_RUNNER_STRATEGY must be one of "
                f"{_STRATEGIES}, got {env!r}")
        return env
    return "deferred"


def resolve_strategy(strategy: Optional[str],
                     max_inflight: Optional[int]) -> Tuple[str, int]:
    """Validate/default the (strategy, max_inflight) pair — shared by
    BatchRunner and ShardedBatchRunner so both reject typos and agree on
    the immediate == zero-queue equivalence.

    An explicit positive ``max_inflight`` with no explicit strategy
    means the caller wants a queue — that selects ``deferred`` rather
    than being silently discarded by the auto-default; combining it with
    an explicit ``strategy='immediate'`` is a contradiction and raises.
    """
    if strategy is None and max_inflight is not None \
            and not os.environ.get("SPARKDL_TPU_RUNNER_STRATEGY"):
        # (an explicit env strategy still wins — a contradiction with
        # max_inflight then errors below, loudly)
        strategy = "deferred" if max_inflight > 0 else "immediate"
    strategy = strategy or _default_strategy()
    if strategy not in _STRATEGIES:
        raise ValueError(
            f"strategy must be one of {_STRATEGIES}, got {strategy!r}")
    if strategy == "immediate":
        if max_inflight is not None and max_inflight > 0:
            raise ValueError(
                f"strategy='immediate' means a zero-length queue; "
                f"max_inflight={max_inflight} contradicts it (use "
                "strategy='deferred' for a bounded queue)")
        return strategy, 0
    if max_inflight is not None:
        return strategy, max_inflight
    return strategy, (MAX_INFLIGHT_HOST_ASYNC
                      if strategy in ("host_async", "prefetch")
                      else MAX_INFLIGHT_BATCHES)


def resolve_prefetch_depth(depth: Optional[int]) -> int:
    """Validate/default the "prefetch" strategy's input look-ahead
    depth: how many chunks ahead of the dispatching one are kept
    ``device_put`` at once (other strategies carry but ignore it).
    An explicit ctor value wins, then ``SPARKDL_TPU_PREFETCH_DEPTH``,
    then :data:`DEFAULT_PREFETCH_DEPTH`."""
    if depth is None:
        env = os.environ.get("SPARKDL_TPU_PREFETCH_DEPTH")
        if not env:
            return DEFAULT_PREFETCH_DEPTH
        try:
            depth = int(env)
        except ValueError:
            raise ValueError(
                f"SPARKDL_TPU_PREFETCH_DEPTH must be a positive int, "
                f"got {env!r}") from None
    if depth < 1:
        raise ValueError(f"prefetch_depth must be >= 1, got {depth}")
    return int(depth)


def _ring_env_int(name: str, default: int) -> int:
    """Integer env knob for the infeed-ring family that DEGRADES on a
    typo instead of raising (contrast :func:`resolve_prefetch_depth`,
    which predates the ring): the ring is a perf layer a bad env var
    must not take the pipeline down with — the degrade is loud
    (warn_once + ``ship.ring_config_errors``), never silent."""
    env = os.environ.get(name)
    if env is None or env == "":
        return default
    try:
        return int(env)
    except ValueError:
        warn_once(f"config:{name}",
                  "%s must be an integer, got %r; running with the "
                  "default %d (counted in ship.ring_config_errors)",
                  name, env, default)
        default_registry().counter("ship.ring_config_errors").add()
        return default


def resolve_infeed_ring(depth: Optional[int]) -> int:
    """Validate/default the device-resident infeed ring depth: 0 is
    off, K >= 2 engages a K-slot ring (:class:`InfeedRing`). An
    explicit ctor value wins, then ``SPARKDL_TPU_INFEED_RING``, then
    :data:`DEFAULT_INFEED_RING`. Invalid values degrade loudly to a
    working shape instead of raising (``_ring_env_int`` rationale):
    negatives fall back to the default, 1 clamps up to the K=2
    double-buffer floor — both counted in ``ship.ring_config_errors``."""
    if depth is None:
        depth = _ring_env_int("SPARKDL_TPU_INFEED_RING",
                              DEFAULT_INFEED_RING)
    depth = int(depth)
    if depth < 0:
        warn_once("config:infeed_ring_negative",
                  "infeed_ring %d is negative; ring stays off "
                  "(counted in ship.ring_config_errors)", depth)
        default_registry().counter("ship.ring_config_errors").add()
        return DEFAULT_INFEED_RING
    if depth == 1:
        warn_once("config:infeed_ring_floor",
                  "infeed_ring 1 cannot double-buffer (a 1-slot ring "
                  "evicts on every miss); clamped up to the K=2 floor "
                  "(counted in ship.ring_config_errors)")
        default_registry().counter("ship.ring_config_errors").add()
        return 2
    return depth


def resolve_transfer_interleave(width: Optional[int]) -> int:
    """Validate/default the per-device transfer interleave width: 0
    (and 1, which IS serial) mean FIFO placement behind one stream;
    >= 2 engages :func:`interleaved_device_put` for sharded
    placements. Ctor value, then ``SPARKDL_TPU_TRANSFER_INTERLEAVE``,
    then :data:`DEFAULT_TRANSFER_INTERLEAVE`; negatives degrade loudly
    to the default (``ship.ring_config_errors``)."""
    if width is None:
        width = _ring_env_int("SPARKDL_TPU_TRANSFER_INTERLEAVE",
                              DEFAULT_TRANSFER_INTERLEAVE)
    width = int(width)
    if width < 0:
        warn_once("config:transfer_interleave_negative",
                  "transfer_interleave %d is negative; interleave "
                  "stays off (counted in ship.ring_config_errors)",
                  width)
        default_registry().counter("ship.ring_config_errors").add()
        return DEFAULT_TRANSFER_INTERLEAVE
    if width == 1:
        return 0  # width 1 is definitionally the serial stream
    return width


# once-per-process-per-reason degrade warnings (the imageIO
# fused-fallback precedent): a long degraded stream — e.g. a serve
# dispatcher running thousands of runner dispatches against a backend
# without async placement — must not re-log the same degrade per run.
# The registry's ship.degrade_events counter keeps the per-event
# record; the log keeps the first occurrence per reason.
_WARNED_REASONS: set = set()


def warn_once(reason: str, msg: str, *args) -> None:
    """Log ``msg`` at WARNING exactly once per process per ``reason``
    key — every runner degrade path funnels through this so new
    degrade reasons inherit the dedupe. Inside a telemetry-armed
    pipeline worker process the event ships to the parent instead
    (which dedupes ACROSS workers and logs once,
    :mod:`sparkdl_tpu.obs.remote`); everywhere else the hook is one
    module-global ``None`` check."""
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
class _RingSlot:
    """One retained infeed-ring slab: the content fingerprint, the
    pre-placed device buffers, and the bookkeeping the hit/evict/
    donate policy runs on. ``donated`` marks a slab whose buffers were
    donated into a jitted call — dead device memory that must never be
    handed out again (:meth:`InfeedRing.get` raises)."""

    fp: bytes
    placed: Dict[str, jax.Array]
    nbytes: int
    hits: int = 0
    donated: bool = False
    last_used: int = 0


class InfeedRing:
    """Persistent device-resident infeed ring: K content-addressed
    pre-placed chunk slabs — :class:`PadStaging`'s device-side sibling
    (staging owns the HOST tail buffer; the ring owns the PLACED
    slabs), grown per runner and reused across ``run()`` calls.

    Policy (dispatch_chunks drives it per chunk):

    * **hit** — the chunk's content fingerprint matches a live slot:
      the RESIDENT slab dispatches (undonated — it must survive for
      the next hit) and zero bytes cross the link
      (``ship.ring_hits`` / ``ship.bytes_resident``).
    * **miss, slot available** — the placed chunk is RETAINED: empty
      capacity first, then slabs already consumed by donation, then a
      stale slot (no hit or refresh for >= 2*depth dispatches — how
      the ring adapts when a mid-stream ``LiveBatchHint`` changes the
      chunk shape and old-shape slots can never hit again).
    * **miss, every slot recently useful** — the chunk streams
      through with its buffers DONATED into the jitted call
      (``ship.ring_donations``) so steady-state HBM is reused for the
      outputs instead of double-buffering; the hot resident set is
      never evicted for one-shot traffic.

    ``note_shipped`` keeps a bounded fingerprint history of everything
    that crossed the link, so shipping the SAME content twice is
    counted (``ship.bytes_reshipped``) — the waste the ring exists to
    kill, gated to 0 on a steady repeated-corpus pass (tools/ci.sh).

    Single-threaded by contract: a runner checks its ring out under a
    try-lock and a concurrent ``run()`` on the same runner bypasses
    the ring entirely (ships normally) instead of racing on slot
    state — the :func:`checkout_staging` discipline, no lock inside.
    """

    def __init__(self, depth: int):
        if depth < 2:
            raise ValueError(f"ring depth must be >= 2, got {depth}")
        self.depth = int(depth)
        self._slots: List[_RingSlot] = []
        self._index: Dict[bytes, int] = {}
        # bounded LRU fingerprint history of shipped content — the
        # bytes_reshipped detector survives slot eviction
        self._shipped: "collections.OrderedDict[bytes, None]" = \
            collections.OrderedDict()
        self._clock = 0
        self._victim = 0
        # the owning runner's checkout lock, attached by
        # _checkout_ring; a bare ring (unit tests, single-threaded
        # use) carries None and the sanitizer contract check stays off
        self._guard: Optional[threading.Lock] = None

    def fingerprint(self, chunk: Dict[str, np.ndarray]) -> bytes:
        """Content address of one host chunk (name+dtype+shape+bytes,
        blake2b-128): computed only while a ring is engaged — the hash
        is the toll a content hit pays instead of the link transfer."""
        h = hashlib.blake2b(digest_size=16)
        for k in sorted(chunk):
            v = np.asarray(chunk[k])
            if not v.flags.c_contiguous:
                v = np.ascontiguousarray(v)
            h.update(k.encode())
            h.update(str(v.dtype).encode())
            h.update(str(v.shape).encode())
            h.update(v)
        return h.digest()

    def tick(self) -> None:
        """One dispatch observed — the idle-age clock evictions key on."""
        self._clock += 1

    def get(self, fp: bytes) -> Optional[Dict[str, jax.Array]]:
        """The resident slab for ``fp``, or None. Raises on a slot
        consumed by donation: handing out donated buffers is a read of
        dead device memory — the runtime use-after-donate guard
        backing the static H15 donation-safety analysis."""
        if self._guard is not None:
            assert_lock_owned(self._guard, "InfeedRing.get")
        i = self._index.get(fp)
        if i is None:
            return None
        slot = self._slots[i]
        if slot.donated:
            raise RuntimeError(
                "use-after-donate: infeed ring slot for fingerprint "
                f"{fp.hex()[:12]} was donated into a jitted call; its "
                "device buffers are dead and must never be re-read")
        slot.hits += 1
        slot.last_used = self._clock
        return slot.placed

    def note_shipped(self, fp: bytes) -> bool:
        """Record ``fp`` as having crossed the link; True when it had
        ALREADY crossed before (a re-ship, counted by the caller)."""
        seen = fp in self._shipped
        self._shipped[fp] = None
        if seen:
            self._shipped.move_to_end(fp)
        cap = max(64, 8 * self.depth)
        while len(self._shipped) > cap:
            self._shipped.popitem(last=False)
        return seen

    def note_donated(self, fp: bytes) -> None:
        """Mark ``fp``'s retained slot consumed-by-donation: any later
        :meth:`get` of it raises instead of returning dead buffers."""
        if self._guard is not None:
            assert_lock_owned(self._guard, "InfeedRing.note_donated")
        i = self._index.get(fp)
        if i is not None:
            self._slots[i].donated = True

    def admit(self, fp: bytes, placed: Dict[str, jax.Array],
              nbytes: int) -> bool:
        """Try to retain a just-placed chunk. True = retained (the
        caller dispatches UNDONATED — the slab must stay alive); False
        = every slot is recently useful, stream the chunk through
        (donate) rather than evicting a hot slab."""
        if self._guard is not None:
            assert_lock_owned(self._guard, "InfeedRing.admit")
        for i, slot in enumerate(self._slots):
            if slot.donated:        # dead slab: reclaim first
                self._install(i, fp, placed, nbytes)
                return True
        if len(self._slots) < self.depth:
            self._index[fp] = len(self._slots)
            self._slots.append(_RingSlot(fp, placed, nbytes,
                                         last_used=self._clock))
            return True
        for off in range(self.depth):
            i = (self._victim + off) % self.depth
            if self._clock - self._slots[i].last_used \
                    >= 2 * self.depth:
                self._victim = (i + 1) % self.depth
                self._install(i, fp, placed, nbytes)
                return True
        return False

    def _install(self, i: int, fp: bytes,
                 placed: Dict[str, jax.Array], nbytes: int) -> None:
        self._index.pop(self._slots[i].fp, None)
        self._slots[i] = _RingSlot(fp, placed, nbytes,
                                   last_used=self._clock)
        self._index[fp] = i

    def retire_all(self) -> None:
        """Back-date every slot's last-used clock so each is
        immediately reclaimable by :meth:`admit` — called by warmup
        after it fills the ring with synthetic batches, so the first
        REAL corpus never donates-through behind warmup slabs (their
        placement warmth is spent; their content will never hit). The
        slots still serve hits until actually evicted."""
        for slot in self._slots:
            slot.last_used = self._clock - 2 * self.depth

    def resize(self, depth: int) -> None:
        """Adopt a new depth between runs (the autotune knob's apply
        point). Shrinking drops the highest slots; growing keeps every
        resident slab."""
        depth = int(depth)
        if depth < 2:
            raise ValueError(f"ring depth must be >= 2, got {depth}")
        if depth == self.depth:
            return
        if depth < len(self._slots):
            del self._slots[depth:]
            self._index = {s.fp: i for i, s in enumerate(self._slots)}
        self.depth = depth
        self._victim = 0

    def state(self) -> dict:
        """Live ring shape for telemetry (the serve layer's per-model
        ``runner`` dict on ``/statusz``)."""
        live = [s for s in self._slots if not s.donated]
        return {
            "depth": int(self.depth),
            "slots": len(self._slots),
            "live": len(live),
            "donated": sum(1 for s in self._slots if s.donated),
            "resident_bytes": int(sum(s.nbytes for s in live)),
            "hits": int(sum(s.hits for s in self._slots)),
        }


@dataclass
class ShipStats:
    """Per-run link-byte accounting for ring-engaged dispatches,
    handed into :func:`dispatch_chunks` by the runner and fed to
    :func:`record_run_feeds` as the ``shipped_bytes`` override: the
    ledger's link lane then sees the bytes that actually CROSSED the
    link, with content-hit reuse accounted separately
    (``resident_bytes``) instead of inflating link utilization. Plain
    data, no lock: one accumulator belongs to one run() call."""

    shipped_bytes: int = 0
    resident_bytes: int = 0
    hits: int = 0
    misses: int = 0
    donated: int = 0


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
    ship-side stall the overlap strategies exist to hide."""

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


def dispatch_chunks(fn, params, chunks, strategy: str, max_inflight: int,
                    sink: SlabSink, place=None, sharding=None,
                    prefetch_depth: int = DEFAULT_PREFETCH_DEPTH,
                    phases: Optional[ChunkPhases] = None,
                    ring: Optional[InfeedRing] = None,
                    donate_fn=None, interleave: int = 0,
                    stats: Optional[ShipStats] = None,
                    carry: Optional[CarryWindow] = None) -> int:
    """THE dispatch state machine, shared by BatchRunner._run_device
    and ShardedBatchRunner.run (one copy of the trickiest loop in the
    codebase: generator look-ahead, placed-chunk hand-off, bounded
    drain, the window carried over a ``run()`` boundary). Returns the
    number of batches of this run's inputs.

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
    ``deferred`` and ``host_async`` carry; ``immediate`` has nothing in
    flight to carry under; ``prefetch`` and an engaged ring drain as
    they always did. Without ``carry`` the call dispatches and drains
    exactly as it did before the carry existed.

    ``place`` (optional) explicitly device_puts a chunk at dispatch —
    the sharded runner's multi-process requirement. ``sharding``
    (optional) is passed to :func:`start_device_prefetch` so prefetched
    chunks land with the data sharding instead of committed to one
    device. ``prefetch_depth`` (prefetch strategy only) bounds the
    input look-ahead: up to that many chunks ahead of the dispatching
    one are kept ``device_put`` at once in a shared FIFO, so a link
    whose latency exceeds one chunk's compute still arrives resident —
    at the cost of ``prefetch_depth`` chunk-sized device buffers on top
    of the ``max_inflight`` result queue. ``phases`` (optional)
    accumulates per-chunk placement/enqueue timestamps for the serve
    layer's request timelines (:class:`ChunkPhases`); the drain half
    is the sink's ``transfer_wait``, folded in by the caller.

    ``ring`` (optional :class:`InfeedRing`) engages the
    device-resident infeed ring: every chunk routes through
    content-addressed hit/retain/donate policy (class docstring) —
    with ``donate_fn`` (the donated jitted program) stream-through
    chunks donate their input buffers. ``interleave`` >= 2 places the
    per-device legs of sharded placements concurrently
    (:func:`interleaved_device_put`). ``stats`` (optional
    :class:`ShipStats`) accumulates this run's net link bytes for the
    caller's :func:`record_run_feeds` override. All three default off
    — the pre-ring call shape is unchanged."""
    host_async = strategy in ("host_async", "prefetch")
    prefetch = strategy == "prefetch"
    lookahead = max(1, int(prefetch_depth))
    pending: collections.deque = (carry.head if carry is not None
                                  else collections.deque())
    # the depth-N input look-ahead: (valid, payload, donate, counted)
    # tuples whose host→device transfer start_device_prefetch/ring
    # routing already kicked off (donate marks ring stream-through
    # chunks whose buffers the jitted call consumes; counted says the
    # ring already booked its link bytes)
    ahead: collections.deque = collections.deque()
    exhausted = False
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

    def pull():
        nonlocal exhausted
        nxt = next(chunks, None)
        if nxt is None:
            exhausted = True
        return nxt

    def route(valid, chunk):
        """Route one pulled chunk through the engaged ring: returns
        (payload, donate). A content hit dispatches the RESIDENT slab
        — zero bytes cross the link; a miss places the chunk and
        either retains it (free/reclaimable slot) or streams it
        through donated."""
        ring.tick()
        fp = ring.fingerprint(chunk)
        nbytes = sum(int(getattr(v, "nbytes", 0))
                     for v in chunk.values())
        resident = ring.get(fp)
        if resident is not None:
            reg.counter("ship.ring_hits").add()
            reg.counter("ship.bytes_resident").add(nbytes)
            if stats is not None:
                stats.hits += 1
                stats.resident_bytes += nbytes
            return resident, False
        reg.counter("ship.ring_misses").add()
        if stats is not None:
            stats.misses += 1
            stats.shipped_bytes += nbytes
        if ring.note_shipped(fp):
            # the same content crossed the link before — the waste the
            # ring exists to kill; reads 0 on a steady repeated-corpus
            # pass (tools/ci.sh gates it)
            reg.counter("ship.bytes_reshipped").add(nbytes)
        src = chunk
        if _placement_may_alias():
            # CPU backends may zero-copy alias the host numpy buffer
            # into the placed array, and the pad-staging tail buffer is
            # rewritten next run — a retained slab must OWN its bytes
            # or a later hit would read silently mutated content
            src = {k: np.array(v) for k, v in chunk.items()}
        put_t0 = time.perf_counter() if phases is not None else 0.0
        with span("device_put", lane="ship", rows=valid, ring="miss"):
            placed = start_device_prefetch(src, sharding,
                                           interleave=interleave)
        if phases is not None:
            phases.device_put_s += time.perf_counter() - put_t0
        if ring.admit(fp, placed, nbytes):
            # retained: dispatch UNDONATED — the slab must stay alive
            # for the next content hit
            return placed, False
        # every slot recently useful: stream through, donating the
        # placed buffers into the call so their HBM is reused for the
        # outputs instead of double-buffering one-shot traffic
        return placed, donate_fn is not None

    def launch(valid, chunk, into, placed_ok=False, donate=False,
               counted=False, **whose):
        """Place (where the caller must) and enqueue one chunk; its
        result joins ``into``."""
        watchdog_pulse(wd_source)
        # fault-injection site: one chunk's input-side placement/
        # dispatch (strategy-independent, so drills hit every
        # backend the same way; disarmed: one armed-check)
        maybe_fail("ship.device_put")
        if not placed_ok and place is not None:
            put_t0 = time.perf_counter() if phases is not None else 0.0
            with span("device_put", lane="ship", rows=valid):
                chunk = place(chunk)
            if phases is not None:
                phases.device_put_s += time.perf_counter() - put_t0
        if stats is not None and not counted:
            # chunks dispatched outside the ring still cross the
            # link — keep the net-bytes account whole-run honest
            stats.shipped_bytes += sum(
                int(getattr(v, "nbytes", 0)) for v in chunk.values())
        # NOTE: on async backends this span times the ENQUEUE of
        # the jitted call, not device compute — device-side time is
        # only host-observable at the drain (the device_get span)
        enq_t0 = time.perf_counter() if phases is not None else 0.0
        with span("dispatch", lane="ship", rows=valid, **whose):
            if donate and donate_fn is not None:
                res, donated_now = dispatch_donated(
                    donate_fn, fn, params, chunk)
                if donated_now:
                    reg.counter("ship.ring_donations").add()
                    if stats is not None:
                        stats.donated += 1
            else:
                res = fn(params, chunk)
        if phases is not None:
            phases.enqueue_s += time.perf_counter() - enq_t0
        if host_async:
            start_host_copies(res)
        into.append((valid, res))

    with watchdog_watch(wd_source):
        while True:
            # keep the look-ahead full: start the host→device transfer
            # of up to ``lookahead`` chunks BEYOND the one about to
            # dispatch, so the transfers proceed while the device
            # computes (depth 1 == the classic place-i+1-during-i)
            while prefetch and not exhausted and len(ahead) < lookahead:
                nxt = pull()
                if nxt is None:
                    break
                if ring is not None:
                    ahead.append((nxt[0],) + route(nxt[0], nxt[1])
                                 + (True,))
                    continue
                put_t0 = time.perf_counter() if phases is not None \
                    else 0.0
                with span("device_put", lane="ship", rows=nxt[0],
                          prefetch=True, ahead=len(ahead) + 1):
                    placed = start_device_prefetch(
                        nxt[1], sharding, interleave=interleave)
                if phases is not None:
                    phases.device_put_s += time.perf_counter() - put_t0
                ahead.append((nxt[0], placed, False, False))
            if ahead:
                valid, chunk, donate, counted = ahead.popleft()
                placed_ok = True
            else:
                nxt = pull()
                if nxt is None:
                    break
                valid = nxt[0]
                if ring is not None:
                    chunk, donate = route(valid, nxt[1])
                    placed_ok = counted = True
                else:
                    chunk = nxt[1]
                    placed_ok = donate = counted = False
            launch(valid, chunk, pending, placed_ok, donate, counted)
            batches += 1
            depth.set(len(pending))
            depth_peak.set_max(len(pending))
            drain_bounded(pending, sink, max_inflight)
            depth.set(len(pending))
        upcoming = None
        if carry is not None and pending and not prefetch \
                and ring is None:
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


def start_host_copies(res: Dict[str, jax.Array]) -> None:
    """Kick off async device→host copies for every output of an
    enqueued result (the "host_async" strategy's enqueue hook)."""
    for v in res.values():
        v.copy_to_host_async()


def start_device_prefetch(chunk: Dict[str, np.ndarray], sharding=None,
                          interleave: int = 0
                          ) -> Dict[str, jax.Array]:
    """``jax.device_put`` an upcoming chunk so its host→device transfer
    overlaps the CURRENT chunk's compute (the "prefetch" strategy's
    input hook; ``dispatch_chunks`` keeps up to ``prefetch_depth`` of
    these in flight); the jitted call then consumes an
    already-resident buffer instead of transferring at dispatch time.
    ``interleave`` >= 2 with a multi-device ``sharding`` routes through
    :func:`interleaved_device_put` (per-device transfer streams instead
    of FIFO behind one); its degrade falls back to the serial path
    HERE."""
    if sharding is not None and interleave >= 2:
        placed = interleaved_device_put(chunk, sharding, interleave)
        if placed is not None:
            return placed
        # interleave degraded (counted there): serial FIFO below
    if sharding is not None:
        return {k: jax.device_put(v, sharding)
                for k, v in chunk.items()}
    return {k: jax.device_put(v) for k, v in chunk.items()}


# lazily probed once: CPU backends may alias host numpy memory into
# "device" arrays, so ring-retained slabs defensively copy (route()).
_MAY_ALIAS: Optional[bool] = None


def _placement_may_alias() -> bool:
    global _MAY_ALIAS
    if _MAY_ALIAS is None:
        _MAY_ALIAS = jax.default_backend() == "cpu"
    return _MAY_ALIAS


# donation-support verdict, probed once per process by the FIRST
# donated dispatch: platforms whose donation is a no-op (CPU) execute
# the donated program correctly but warn that the donated buffers were
# not usable — that verdict degrades every later ring stream-through
# to the undonated program, counted + warned, never silent. Tests
# reset by replacing the dict (module-global, same discipline as
# _WARNED_REASONS).
_DONATION_STATE = {"probed": False, "supported": True}


def dispatch_donated(donate_fn, fn, params, chunk):
    """Dispatch one ring stream-through chunk, donating its input
    buffers when the platform supports donation: ``(result,
    donated)``. The first call probes — it runs ``donate_fn`` under a
    warning trap; JAX's "donated buffers were not usable" UserWarning
    is the no-op verdict (the buffers stayed alive, HBM was NOT
    reused) and flips the process to undonated dispatch
    (``ship.ring_degrade_events``). Semantics are identical either
    way — only the memory claim changes, and the degrade makes sure
    the claim is never silently false."""
    if _DONATION_STATE["probed"]:
        if _DONATION_STATE["supported"]:
            return donate_fn(params, chunk), True
        return fn(params, chunk), False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = donate_fn(params, chunk)
    _DONATION_STATE["probed"] = True
    if any("donated" in str(w.message).lower() for w in caught):
        _DONATION_STATE["supported"] = False
        warn_once("degrade:ring_donation",
                  "backend cannot donate input buffers (donation is a "
                  "no-op on this platform); infeed ring degrades to "
                  "undonated stream-through — steady-state HBM is NOT "
                  "reclaimed per chunk")
        default_registry().counter("ship.ring_degrade_events").add()
        return res, False
    return res, True


# shared bounded pool for the per-device transfer legs: one pool per
# process (grown to the widest requested width), never per chunk —
# thread startup on the hot path would cost more than the serialized
# stream it replaces
_INTERLEAVE_POOL: Optional[ThreadPoolExecutor] = None
_INTERLEAVE_POOL_LOCK = threading.Lock()


def _interleave_pool(width: int) -> ThreadPoolExecutor:
    global _INTERLEAVE_POOL
    with _INTERLEAVE_POOL_LOCK:
        pool = _INTERLEAVE_POOL
        if pool is None or pool._max_workers < width:
            _INTERLEAVE_POOL = ThreadPoolExecutor(
                max_workers=width,
                thread_name_prefix="sparkdl-interleave")
        return _INTERLEAVE_POOL


def interleaved_device_put(chunk: Dict[str, np.ndarray], sharding,
                           width: int
                           ) -> Optional[Dict[str, jax.Array]]:
    """Place one chunk's arrays with per-device transfer streams: each
    device's shard is ``device_put`` on its own pool thread instead of
    every per-device leg queueing FIFO behind one stream, then the
    global array is assembled zero-copy from the landed shards
    (``jax.make_array_from_single_device_arrays``). ``width`` bounds
    the concurrent legs. Shardings addressing fewer than 2 devices
    take the serial path silently — there is nothing to interleave,
    that is a no-op, not a degrade.

    Returns None on degrade — a backend/sharding combination the
    shard-wise placement cannot serve — counted via ``warn_once`` +
    ``ship.degrade_events`` + ``ship.interleave_degrade_events``,
    never silent; the caller (:func:`start_device_prefetch`) then
    falls back to the serial FIFO placement."""
    try:
        pool = _interleave_pool(min(int(width), 16))
        out: Dict[str, jax.Array] = {}
        for k, v in chunk.items():
            shape = np.shape(v)
            idx_map = sharding.addressable_devices_indices_map(shape)
            if len(idx_map) < 2:
                out[k] = jax.device_put(v, sharding)
                continue
            futs = [pool.submit(jax.device_put, v[idx], d)
                    for d, idx in idx_map.items()]
            shards = [f.result() for f in futs]
            out[k] = jax.make_array_from_single_device_arrays(
                shape, sharding, shards)
        return out
    # sparkdl-lint: allow[H12] -- probe-and-degrade: an unservable backend/sharding combination is the probe verdict; the fallthrough records warn_once + ship.degrade_events + ship.interleave_degrade_events and the caller takes the serial path
    except (NotImplementedError, ValueError, TypeError, KeyError,
            AttributeError) as e:
        warn_once("degrade:no_interleave",
                  "per-device transfer interleave unavailable on this "
                  "backend/sharding (%s); placements degrade to the "
                  "serial FIFO stream", repr(e))
        default_registry().counter("ship.degrade_events").add()
        default_registry().counter(
            "ship.interleave_degrade_events").add()
        return None


def record_run_feeds(model_fn: ModelFunction,
                     inputs: Dict[str, np.ndarray],
                     elapsed_s: float, wait_s: float,
                     batches: int = 0,
                     flops_per_batch: Optional[float] = None,
                     shipped_bytes: Optional[int] = None) -> None:
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
    traffic lands in the same roofline.

    ``shipped_bytes`` (optional) overrides the input-sum byte count
    with the bytes that actually CROSSED the link — ring-engaged runs
    pass their :class:`ShipStats` total, so the ledger's link lane
    subtracts ring-resident reuse (content hits dispatch resident
    slabs and ship nothing; the reuse lands in ``ship.bytes_resident``
    instead of inflating ``ledger.util.link``)."""
    reg = default_registry()
    reg.counter("device.run_seconds").add(elapsed_s)
    reg.counter("ship.transfer_wait_seconds_total").add(wait_s)
    if flops_per_batch and batches:
        reg.counter("device.flops_total").add(
            float(flops_per_batch) * batches)
    if model_fn.backend != "host":
        if shipped_bytes is None:
            # getattr: array-likes without nbytes (exotic duck-typed
            # inputs) ship unknown bytes — an under-count, never a
            # crash
            shipped_bytes = sum(int(getattr(v, "nbytes", 0))
                                for v in inputs.values())
        reg.counter("ship.bytes_shipped").add(int(shipped_bytes))


@dataclass
class RunnerMetrics:
    """Throughput + host-copy counters (SURVEY §5: the reference had
    none — these exist to prove the north-star number, and since the
    pipeline went link-bound, to prove the ship-path copies went away
    rather than asserting it).

    ``bytes_staged``: input bytes written through the reusable
    pad-staging buffer (tail chunks only). ``bytes_copied``: input
    bytes copied to make chunks contiguous — exactly 0 for
    batch-aligned contiguous device runs, the zero-copy hot path.
    ``transfer_wait_seconds``: time blocked in ``device_get`` drains
    (the ship-side stall the overlap strategies hide)."""

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
                 strategy: Optional[str] = None,
                 max_inflight: Optional[int] = None,
                 prefetch_depth: Optional[int] = None,
                 infeed_ring: Optional[int] = None,
                 transfer_interleave: Optional[int] = None):
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        self.model_fn = model_fn
        self.batch_size = batch_size
        self.metrics = metrics or RunnerMetrics()
        # immediate == a zero-length queue; deferred keeps a small one
        self.strategy, self.max_inflight = resolve_strategy(
            strategy, max_inflight)
        # depth-N input look-ahead for the "prefetch" strategy; carried
        # (ignored) by the others so a live strategy change keeps it
        self.prefetch_depth = resolve_prefetch_depth(prefetch_depth)
        # device-resident infeed ring (0 = off) and per-device transfer
        # interleave width (0 = serial) — like strategy/depth, read
        # afresh per run() so the autotune controller can move them
        self.infeed_ring = resolve_infeed_ring(infeed_ring)
        self.transfer_interleave = resolve_transfer_interleave(
            transfer_interleave)
        # persistent pad staging, reused across run() calls; checked
        # out under a try-lock so concurrent run() calls on one runner
        # fall back to a private throwaway stager instead of racing
        self._staging = PadStaging()
        self._staging_lock = threading.Lock()
        # the persistent ring, created on the first engaged run; its
        # try-lock discipline mirrors staging, except a contended
        # run() BYPASSES the ring (ships normally) instead of using a
        # throwaway — a private ring could never produce hits worth
        # its slab memory
        self._ring: Optional[InfeedRing] = None
        self._ring_lock = threading.Lock()
        # the in-flight window between two run() calls
        self._carry = BoundaryCarry()

    def _checkout_staging(self) -> Tuple[PadStaging, bool]:
        return checkout_staging(self._staging, self._staging_lock)

    def drop_carry(self) -> None:
        """Forget device batches a ``run(..., upcoming=...)`` of this
        thread left in flight for a run that will not come (the engine
        calls it when a stream ends or is abandoned)."""
        self._carry.drop()

    def _checkout_ring(self):
        """(ring, donate_fn, locked, stats) for this run: the
        persistent ring when engaged (``infeed_ring`` >= 2, jax
        backend) and uncontended, else all-None/False — a concurrent
        run() on the same runner ships normally instead of racing on
        slot state. Resizes the live ring when the autotune knob moved
        between runs, publishes the ``ship.ring_depth`` /
        ``ship.interleave_width`` gauges, and builds the donated
        jitted program stream-through chunks dispatch into."""
        depth = int(self.infeed_ring)
        if depth < 2 or self.model_fn.backend != "jax":
            return None, None, False, None
        if not self._ring_lock.acquire(blocking=False):
            return None, None, False, None
        if self._ring is None:
            self._ring = InfeedRing(depth)
        else:
            self._ring.resize(depth)
        # arm the sanitizer's caller-holds check: every ring mutation
        # from here on must happen while this checkout hold is live
        self._ring._guard = self._ring_lock
        reg = default_registry()
        reg.gauge("ship.ring_depth").set(depth)
        reg.gauge("ship.interleave_width").set(
            int(self.transfer_interleave))
        donate_fn = self.model_fn.jitted(donate_inputs=True)
        return self._ring, donate_fn, True, ShipStats()

    def ring_state(self) -> Optional[dict]:
        """Live infeed-ring telemetry (None when no ring has engaged)
        — surfaced per model in the serve layer's ``/statusz`` runner
        dict."""
        ring = self._ring
        return ring.state() if ring is not None else None

    # Locks (and warm staging buffers / resident ring slabs) don't
    # pickle; device stage closures holding a runner ship to Spark
    # executors (spark_binding) — same discipline as RunnerMetrics.
    # The ring rebuilds empty on arrival: slabs are device memory and
    # never cross process boundaries.
    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_staging", None)
        state.pop("_staging_lock", None)
        state.pop("_ring", None)
        state.pop("_ring_lock", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._staging = PadStaging()
        self._staging_lock = threading.Lock()
        self._ring = None
        self._ring_lock = threading.Lock()

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
        with span("runner.run", lane="ship", rows=n,
                  strategy=self.strategy):
            t0 = time.perf_counter()
            counters = CopyCounters()
            # ONE snapshot per run: a live controller
            # (sparkdl_tpu/autotune) may move batch_size from another
            # thread between runs — every read below must see the same
            # value or a mid-run shrink would cut chunks on a stale
            # stride and skip rows
            batch_size = self.batch_size
            flops = None
            shipped = None
            began = None
            if self.model_fn.backend == "host":
                out, wait = self._run_host(inputs, n, batch_size)
            else:
                out, wait, stats, began = self._run_device(
                    inputs, n, counters, batch_size, phases, upcoming)
                if stats is not None:
                    # ring-engaged run: the ledger's link lane gets the
                    # bytes that actually crossed the link, net of
                    # resident-slab reuse (record_run_feeds docstring)
                    shipped = stats.shipped_bytes
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
                         batches=batches, flops_per_batch=flops,
                         shipped_bytes=shipped)
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
                               Optional[ShipStats], Optional[str]]:
        fn = self.model_fn.jitted()
        params = self.model_fn.device_params()
        # enqueue then drain to self.max_inflight: 0 = immediate drain,
        # >0 = bounded async dispatch; host_async also starts each
        # result's device→host copy at enqueue; prefetch additionally
        # device_puts upcoming chunks while chunk i computes (module
        # docstring)
        sink = SlabSink(n)
        staging, locked = self._checkout_staging()
        ring, donate_fn, ring_locked, stats = self._checkout_ring()
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
                dispatch_chunks(fn, params, chunks, self.strategy,
                                self.max_inflight, sink,
                                prefetch_depth=self.prefetch_depth,
                                phases=phases, ring=ring,
                                donate_fn=donate_fn,
                                interleave=self.transfer_interleave,
                                stats=stats, carry=carry)
        finally:
            if ring_locked:
                self._ring_lock.release()
            if locked:
                self._staging_lock.release()
        if phases is not None:
            # the drain half: the same clock reads as
            # transfer_wait_seconds (timed_device_get), so the traced
            # and attributed numbers cannot drift
            phases.drain_s += sink.transfer_wait
        return sink.result(), sink.transfer_wait, stats, carry.began

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
    tests.

    Infeed-ring runners (``infeed_ring`` >= 2) warm EVERY ring slot,
    not just one slab shape: K batches of DISTINCT content (the ring
    is content-addressed — identical batches would collide into one
    slot) fill the K slots so no slot's first real use pays a
    placement stall, and one batch PAST capacity streams through the
    donated dispatch so the donated program compiles here, before the
    steady mark, never at a steady-state request. All warm batches
    share the one device shape, so the trace count stays exactly two
    programs (undonated + donated) regardless of K — pinned in
    tests/test_infeed_ring.py."""
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
    ring_depth = int(getattr(runner, "infeed_ring", 0) or 0)
    if ring_depth >= 2:
        # slot 1 holds the zeros batch; slots 2..K get i distinct
        # leading elements flipped to 1 (distinct for every numeric
        # dtype incl. bool); batch K+1 overflows into the donated
        # stream-through path. A collision on degenerate tiny shapes
        # only re-warms a slot — never a failure.
        for i in range(1, ring_depth + 1):
            batch = {}
            for k, (shape, dtype) in sig.items():
                arr = np.zeros((n,) + tuple(shape), dtype)
                flat = arr.reshape(-1)
                flat[:min(i, flat.size)] = 1
                batch[k] = arr
            runner.run(batch)
        # warmup slabs have spent their placement warmth; their
        # synthetic content will never hit — retire them so the first
        # REAL corpus is admitted immediately instead of streaming
        # through for 2*depth dispatches behind them
        ring = getattr(runner, "_ring", None)
        if ring is not None:
            ring.retire_all()
    from sparkdl_tpu.obs.compile_log import compile_log
    compile_log().mark_model_steady(model_fn, reason="warmup_runner")
    return True
