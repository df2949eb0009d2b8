"""The accelerator-host side of the decode fleet: fan-out + ordered
re-merge over the socket transport.

:class:`RemotePipeline` is the client of one or more
:class:`~sparkdl_tpu.inputsvc.server.DecodeServer` workers
(``SPARKDL_TPU_INPUTSVC_WORKERS="host:port,host:port"`` or the
engine's ``inputsvc_endpoints`` ctor arg). Per stream it:

* pings every configured endpoint and DROPS unreachable ones loudly
  (``inputsvc.endpoints_down`` + one warning — a half-provisioned
  fleet streams on what answered; an empty one returns ``None`` so
  :class:`~sparkdl_tpu.data.engine.LocalEngine` falls back to its
  local path, counted in ``inputsvc.fallbacks``);
* fans partitions out round-robin across the live endpoints and
  re-merges fragments strictly in partition order with a bounded
  look-ahead window (the engine's live ``pipeline_read_ahead`` knob)
  — row identity and order are EXACT through the remote path;
* classifies every wire failure TYPED-transient
  (:class:`~sparkdl_tpu.inputsvc.transport.TransportError`, plus the
  ``inputsvc.rpc`` fault site) and re-runs the partition through the
  engine's shared :class:`~sparkdl_tpu.resilience.policy.RetryPolicy`;
  a partition whose transient budget is exhausted — or whose last
  endpoint died mid-stream — FAILS OVER to local decode
  (``inputsvc.local_decodes`` + one warning), so a killed worker
  costs throughput, never a row;
* ingests the telemetry frame riding each result tuple into the
  parent aggregator (``obs/remote.py``) — remote workers land in
  ``/statusz``'s ``workers`` list and the clock-aligned trace merge
  exactly like pool workers — and folds each fragment's reported
  decode busy-seconds into ``engine.busy_seconds`` (the ledger's ONE
  decode-lane feed).

The utilization ledger scales its decode ceiling by the live remote
fleet: this module mirrors the host pipeline's worker bookkeeping
(``inputsvc.workers`` gauge + window/alltime peaks), and
``obs/ledger.py`` ADDS the remote peak to the local pooled peak — N
remote workers are N additional decode lanes beyond the host's own
(``decode_workers`` in every ledger window; docs/DATA_SERVICE.md).
"""

from __future__ import annotations

import logging
import os
import socket
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import pyarrow as pa

from sparkdl_tpu.inputsvc import transport
from sparkdl_tpu.obs import default_registry, span
from sparkdl_tpu.resilience.errors import TransientError
from sparkdl_tpu.resilience.faults import maybe_fail

logger = logging.getLogger(__name__)

#: the fleet env knob: comma-separated ``host:port`` endpoints. Unset =
#: no remote decode; a malformed spec degrades to none with one warning
#: + ``inputsvc.config_errors`` (the repo-wide config-typo discipline)
ENV_ENDPOINTS = "SPARKDL_TPU_INPUTSVC_WORKERS"

#: connect + handshake timeout per endpoint — an unreachable worker
#: must cost seconds at stream START, not a hung stream
CONNECT_TIMEOUT_S = 5.0

#: per-RPC reply timeout: a wedged worker surfaces as a TYPED transient
#: (socket timeout → TransportError) that retries on a live sibling and
#: fails over to local decode — never a silently hung stream
DEFAULT_RPC_TIMEOUT_S = 120.0


def _count(what: str, amount: float = 1.0) -> None:
    default_registry().counter(f"inputsvc.{what}").add(amount)


def resolve_endpoints(explicit=None) -> List[Tuple[str, int]]:
    """The configured fleet: an explicit ctor value (comma string or
    list of ``host:port``) wins, then :data:`ENV_ENDPOINTS`. ANY
    malformed entry degrades the whole spec to no-fleet with one
    warning + ``inputsvc.config_errors`` — a typo'd fleet must never
    make the engine unusable, and silently dropping one endpoint of
    three would quietly re-shape the fleet instead."""
    if explicit is None:
        raw = os.environ.get(ENV_ENDPOINTS, "")
    elif isinstance(explicit, str):
        raw = explicit
    else:
        raw = ",".join(str(e) for e in explicit)
    raw = raw.strip()
    if not raw:
        return []
    out: List[Tuple[str, int]] = []
    for entry in raw.split(","):
        if not entry.strip():
            continue
        ep = transport.parse_endpoint(entry)
        if ep is None:
            logger.warning(
                "%s entry %r is not host:port; remote decode disabled "
                "(fix the full spec — a partial fleet would be a "
                "different deployment than configured)",
                ENV_ENDPOINTS if explicit is None else
                "inputsvc_endpoints", entry)
            _count("config_errors")
            return []
        out.append(ep)
    return out


_warned_once: set = set()
_warn_lock = threading.Lock()


def _warn_once(key: str, msg: str, *args) -> None:
    with _warn_lock:
        fire = key not in _warned_once
        _warned_once.add(key)
    if fire:
        from sparkdl_tpu.obs import remote
        if remote.capture_degrade(f"inputsvc:{key}",
                                  msg % args if args else msg):
            return
        logger.warning(msg, *args)


# the live remote-worker bookkeeping the utilization ledger reads
# (obs/ledger.py): the decode lane's ceiling ADDS the remote fleet's
# window peak to the local pooled peak — same shape, same reasoning as
# data/pipeline.py's _workers_peak (a remote stream that ended
# mid-window already banked its N workers' busy-seconds)
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
    default_registry().gauge("inputsvc.workers").set(live)
    return sid


def _exit_stream(sid: int) -> None:
    with _active_lock:
        entry = _active_streams.pop(sid, None)
        live = max((w for w, _ in _active_streams.values()), default=0)
    default_registry().gauge("inputsvc.workers").set(live)
    if entry is not None:
        _count("stream_seconds", time.perf_counter() - entry[1])


def consume_workers_peak() -> int:
    """Max live remote workers since the previous call — the ledger's
    per-window read (obs/ledger.py), mirroring the host pipeline's
    contract: resets to the current live count so each window consumes
    exactly its own history."""
    global _workers_peak
    with _active_lock:
        live = max((w for w, _ in _active_streams.values()), default=0)
        peak = max(_workers_peak, live)
        _workers_peak = live
        return peak


def alltime_workers_peak() -> int:
    """Process-lifetime remote-worker high-water mark — the ledger's
    cumulative-verdict ceiling component."""
    with _active_lock:
        live = max((w for w, _ in _active_streams.values()), default=0)
        return max(_workers_alltime, live)


# the last-resolved fleet picture, for /statusz and flight bundles
# (one shape everywhere)
_last_state: Dict[str, Any] = {}
_state_lock = threading.Lock()


def _record_state(**kv) -> None:
    with _state_lock:
        _last_state.update(kv)


def state() -> Dict[str, Any]:
    """The scrape-able input-service state (``/statusz`` ``inputsvc``,
    flight bundles): the last stream's resolved fleet + the live
    ``inputsvc.*`` counters (the snapshot tier's counters share the
    prefix and ride along)."""
    snap = default_registry().snapshot()
    with _state_lock:
        out = dict(_last_state)
    with _active_lock:
        out["streams_active"] = len(_active_streams)
        out["workers_live"] = max(
            (w for w, _ in _active_streams.values()), default=0)
    out["counters"] = {k: v for k, v in snap.items()
                       if k.startswith("inputsvc.")}
    return out


class _FleetUnavailable(TransientError):
    """No live endpoint remains for this RPC — transient (a sibling
    retry may land after a reconnect), and past the retry budget the
    caller's local-decode failover owns it."""


class _Endpoint:
    """One connected decode worker: a socket and the lock serializing
    RPCs on it (one in-flight request per connection — the framing has
    no request ids; parallelism comes from the fleet width)."""

    # sparkdl-lint H3 contract: RPCs and death-marking race from the
    # fan-out pool's threads — socket use holds self._lock
    _lock_guards = ("sock", "alive")

    def __init__(self, host: str, port: int,
                 rpc_timeout_s: float = DEFAULT_RPC_TIMEOUT_S):
        self.host = host
        self.port = port
        self.rpc_timeout_s = rpc_timeout_s
        self.sock: Optional[socket.socket] = None
        self.alive = False
        self._lock = threading.Lock()

    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        state["sock"] = None
        state["alive"] = False
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    def connect(self) -> bool:
        """Dial + ping handshake; False (never raises) on an
        unreachable/refusing/mis-speaking peer — stream start owns the
        loud accounting."""
        try:
            sock = socket.create_connection(
                (self.host, self.port), timeout=CONNECT_TIMEOUT_S)
            transport.send_msg(sock, {"op": "ping"})
            header, _ = transport.recv_msg(sock)
            if not header.get("ok"):
                raise transport.TransportError(
                    f"ping rejected: {header!r}")
            sock.settimeout(self.rpc_timeout_s)
        except (OSError, transport.TransportError) as e:
            logger.debug("inputsvc: endpoint %s:%d unreachable: %s",
                         self.host, self.port, e)
            return False
        with self._lock:
            self.sock = sock
            self.alive = True
        return True

    def rpc_decode(self, token: str, plan_blob: bytes, src_blob: bytes,
                   index: int, tel: Optional[dict]) -> tuple:
        """One partition's remote decode → the raw result tuple. Any
        wire failure marks this endpoint dead and raises TYPED
        transient; the caller retries (possibly on a sibling) through
        the engine's shared RetryPolicy."""
        import cloudpickle
        with self._lock:
            sock = self.sock
            if not self.alive or sock is None:
                raise _FleetUnavailable(
                    f"endpoint {self.host}:{self.port} is down")
            try:
                transport.send_msg(
                    sock,
                    {"op": "decode", "token": token, "index": index,
                     "plan_len": len(plan_blob), "tel": tel},
                    plan_blob + src_blob)
                # sparkdl-lint: allow[H8] -- the hold IS the RPC slot: each endpoint socket is a serial request/response channel, so the reply recv must stay inside the lock that serialized the send; fan-out parallelism lives ACROSS endpoints, not on one socket
                header, payload = transport.recv_msg(sock)
            except (OSError, transport.TransportError) as e:
                self._mark_dead_locked()
                _count("rpc_errors")
                if isinstance(e, transport.TransportError):
                    raise
                raise transport.TransportError(
                    f"decode RPC to {self.host}:{self.port} "
                    f"failed: {e}") from e
        if not header.get("ok"):
            _count("rpc_errors")
            raise transport.TransportError(
                f"endpoint {self.host}:{self.port} rejected the "
                f"decode RPC: {header.get('error')!r}")
        _count("bytes", len(payload))
        return cloudpickle.loads(payload)

    def _mark_dead_locked(self) -> None:
        # deferred import mirrors data/pipeline.py: rare path, and the
        # data layer must not pull the jax-importing runtime package
        # at module load
        from sparkdl_tpu.runtime.sanitize import assert_lock_owned
        assert_lock_owned(self._lock, "_Endpoint._mark_dead_locked")
        sock, self.sock = self.sock, None
        # sparkdl-lint: allow[H3] -- caller holds self._lock, asserted by assert_lock_owned above (the _locked-suffix private-helper pattern data/pipeline.py uses)
        self.alive = False
        if sock is not None:
            try:
                sock.close()
            except OSError as e:
                logger.debug("inputsvc: closing a dead endpoint "
                             "socket failed: %s", e)

    def close(self) -> None:
        with self._lock:
            self._mark_dead_locked()

    def is_alive(self) -> bool:
        with self._lock:
            return self.alive


class RemotePipeline:
    """Fan partitions out to the configured decode fleet and re-merge
    fragments in order (module docstring). One instance per stream —
    connections are per-stream, so a shipped/pickled engine never
    carries a live socket (H3)."""

    def __init__(self, endpoints: Sequence[Tuple[str, int]],
                 rpc_timeout_s: float = DEFAULT_RPC_TIMEOUT_S):
        self.endpoints = [_Endpoint(h, p, rpc_timeout_s)
                          for h, p in endpoints]

    def _connect_fleet(self) -> List[_Endpoint]:
        live: List[_Endpoint] = []
        for ep in self.endpoints:
            if ep.connect():
                live.append(ep)
            else:
                _count("endpoints_down")
                _warn_once(
                    f"down:{ep.host}:{ep.port}",
                    "inputsvc: decode worker %s:%d is unreachable; "
                    "streaming on the remaining fleet (local decode "
                    "if none remains)", ep.host, ep.port)
        return live

    def _pickle_payload(self, sources: Sequence, plan: Sequence
                        ) -> Optional[Tuple[bytes, List[bytes]]]:
        """(plan blob, per-source blobs) when the H3 shipping
        discipline holds, else None — the local-fallback trigger (a
        plan that cannot cross a process boundary cannot cross a
        socket either)."""
        import cloudpickle
        try:
            plan_blob = cloudpickle.dumps(list(plan))
            src_blobs = [cloudpickle.dumps(s) for s in sources]
            return plan_blob, src_blobs
        except Exception as e:
            _warn_once(f"pickle:{type(e).__name__}",
                       "inputsvc: plan/source does not survive the "
                       "cloudpickle round-trip (%s: %s); decoding "
                       "locally", type(e).__name__, e)
            _count("fallbacks")
            return None

    def stream(self, sources: Sequence, plan: Sequence, engine
               ) -> Optional[Iterator[Tuple[int, pa.RecordBatch]]]:
        """Yield ``(logical_index, fragment)`` in partition order via
        the remote fleet, or ``None`` when no remote stream can run
        (nothing picklable, or zero endpoints answered) — the engine
        then falls through to its local path, loudly
        (``inputsvc.fallbacks``)."""
        import uuid
        plan = list(plan)
        payload = self._pickle_payload(sources, plan)
        if payload is None:
            return None
        live = self._connect_fleet()
        _record_state(
            endpoints=[f"{ep.host}:{ep.port}" for ep in self.endpoints],
            live_endpoints=[f"{ep.host}:{ep.port}" for ep in live])
        if not live:
            _count("fallbacks")
            _warn_once("fleet-empty",
                       "inputsvc: no configured decode worker is "
                       "reachable; falling back to LOCAL decode (the "
                       "fleet is provisioned but absent — this is a "
                       "deployment problem, not a data one)")
            return None
        plan_blob, src_blobs = payload
        token = uuid.uuid4().hex
        from sparkdl_tpu.obs import remote
        tel = remote.telemetry_config()
        return self._merge(sources, plan, engine, live, plan_blob,
                           src_blobs, token, tel)

    def _merge(self, sources, plan, engine, live, plan_blob, src_blobs,
               token, tel):
        from sparkdl_tpu.data.pipeline import _consume_result
        drain = (any(getattr(st, "effectful", False) for st in plan)
                 or any(getattr(src, "effectful", False)
                        for src in sources))
        rr_lock = threading.Lock()
        rr = [0]

        def _logical(pos: int) -> int:
            logical = getattr(sources[pos], "logical_index", None)
            return pos if logical is None else logical

        def _pick() -> _Endpoint:
            with rr_lock:
                rr[0] += 1
                start = rr[0]
            for i in range(len(live)):
                ep = live[(start + i) % len(live)]
                if ep.is_alive():
                    return ep
            raise _FleetUnavailable(
                "every connected decode worker died mid-stream")

        def _fetch(pos: int) -> pa.RecordBatch:
            logical = _logical(pos)

            def once() -> pa.RecordBatch:
                # the fragment-RPC fault site: the drill that proves
                # zero lost/duplicated rows under a lossy wire
                # (tools/ci.sh; docs/RESILIENCE.md)
                maybe_fail("inputsvc.rpc")
                ep = _pick()
                result = ep.rpc_decode(token, plan_blob,
                                       src_blobs[pos], logical, tel)
                # same consume as the pool transport: frame ingest,
                # typed re-raise of ("err", ...), zero-copy batch
                batch, busy, timings = _consume_result(result)
                default_registry().counter(
                    "engine.busy_seconds").add(busy)
                if engine.stage_metrics is not None:
                    for name, seconds, rows in timings:
                        engine.stage_metrics.add(name, seconds, rows)
                return batch

            try:
                return engine.retry_policy.call(
                    once, key=f"inputsvc:{logical}",
                    on_retry=engine._log_retry(
                        f"remote partition {logical}"))
            except TransientError as exc:
                # retry budget exhausted (or the whole fleet died):
                # LOCAL failover — a dead worker costs throughput,
                # never a row. Loud: counted + one warning; permanent
                # errors propagate typed (a decode that fails on bad
                # data fails locally too — retrying it here would
                # just mask it).
                _count("local_decodes")
                _warn_once("local-failover",
                           "inputsvc: remote decode failed past the "
                           "retry budget (%s); failing over to local "
                           "decode for affected partitions",
                           type(exc).__name__)
                return engine._run_partition(sources[pos], plan, pos)

        def _gen():
            sid = _enter_stream(len(live))
            pool = ThreadPoolExecutor(
                max_workers=len(live),
                thread_name_prefix="sparkdl-inputsvc")
            pending: Dict[int, Future] = {}
            next_to_submit = 0
            next_to_yield = 0
            n = len(sources)
            try:
                while next_to_yield < n:
                    window = max(len(live), int(getattr(
                        engine, "pipeline_read_ahead", 0) or 1))
                    while (next_to_submit < n
                           and len(pending) < window):
                        pending[next_to_submit] = pool.submit(
                            _fetch, next_to_submit)
                        next_to_submit += 1
                    pos = next_to_yield
                    fut = pending.pop(pos)
                    with span("inputsvc.fragment", lane="engine",
                              partition=_logical(pos),
                              workers=len(live)):
                        batch = fut.result()
                    _count("tasks")
                    _count("rows", batch.num_rows)
                    yield _logical(pos), batch
                    next_to_yield += 1
            finally:
                for fut in pending.values():
                    fut.cancel()
                if drain:
                    # the engine's quiesce discipline: an effectful
                    # straggler finishing AFTER the caller's cleanup
                    # corrupts the cleanup's outcome
                    for fut in pending.values():
                        if not fut.cancelled():
                            try:
                                fut.result()
                            except Exception as drain_err:
                                logger.debug(
                                    "inputsvc quiesce drain error: %s",
                                    drain_err)
                pool.shutdown(wait=False, cancel_futures=True)
                for ep in live:
                    ep.close()
                _exit_stream(sid)

        return _gen()
