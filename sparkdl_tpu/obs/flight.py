"""Flight recorder: push-button postmortems for a live or wedged
process.

PR 3's tracer answers "where did the microseconds go" only when a
human arms it and exports a file; the failure mode that motivated the
collective-launch fix (PR 2) presents as a silent hang with zero
forensics. The flight recorder closes that gap: armed, it continuously
retains the last-N spans (the tracer's existing bounded ring — arming
the recorder arms the tracer) while the always-on metrics registry
keeps the rolling counter state, and on demand it writes ONE
self-contained JSON bundle:

* the retained span timeline (Perfetto trace events, drop note
  included) and the full registry snapshot;
* per-session serve queue state (depth, warmup, runner config)
  for every live :class:`ModelServer`;
* the watchdog verdict (:mod:`sparkdl_tpu.obs.watchdog`);
* device/platform info and — where the backend supports it —
  per-device ``memory_stats()`` HBM accounting, degrading gracefully
  on CPU (the sanitizer's probe-and-degrade precedent).

Dump triggers: explicit :meth:`FlightRecorder.dump`, ``SIGUSR2``
(installed when armed — ``kill -USR2 <pid>`` on a wedged process gets
you the bundle without restarting it), an unhandled serve dispatch
failure (:func:`record_failure`, called by the dispatcher's exception
path), and a watchdog stall verdict.

Arming: ``SPARKDL_TPU_FLIGHT=1`` in the environment or
``recorder().arm()`` (the override wins); ``SPARKDL_TPU_FLIGHT_DIR``
names the bundle directory (default: the system temp dir).
:func:`autoarm` applies the env switch's side effects (signal handler
+ span retention) and is called from ``ModelServer.__init__`` so the
common entry point honors the env without any code change. Disarmed there is no signal handler, no tracer arming,
and no per-event cost — only on-demand ``dump()`` still works (it
writes whatever is retained).
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

from sparkdl_tpu.obs.registry import default_registry
from sparkdl_tpu.obs.trace import span, tracer
from sparkdl_tpu.obs.watchdog import watchdog

logger = logging.getLogger(__name__)

_TRUE = ("1", "true", "yes", "on")

#: bundle format tag — bump when the layout changes incompatibly
BUNDLE_SCHEMA = "sparkdl-flight/1"


def _env_armed() -> bool:
    return os.environ.get("SPARKDL_TPU_FLIGHT", "").lower() in _TRUE


def _bundle_dir() -> str:
    d = os.environ.get("SPARKDL_TPU_FLIGHT_DIR", "")
    if d:
        return d
    import tempfile
    return tempfile.gettempdir()


# -- degradable environment probes ------------------------------------------

_platform_cache: Optional[Dict[str, Any]] = None


def platform_info() -> Dict[str, Any]:
    """Backend/device identity for the bundle, probed once and cached;
    a missing or broken backend degrades to an ``error`` entry instead
    of failing the dump (the dump is most valuable exactly when the
    process is unwell)."""
    global _platform_cache
    if _platform_cache is not None:
        return _platform_cache
    info: Dict[str, Any] = {"python": sys.version.split()[0]}
    try:
        import jax
        devices = jax.devices()
        info.update({
            "backend": devices[0].platform if devices else None,
            "device_count": len(devices),
            "devices": [str(d) for d in devices[:8]],
            "jax": getattr(jax, "__version__", None),
        })
    except Exception as e:
        info["error"] = f"{type(e).__name__}: {e}"
    _platform_cache = info
    return info


def memory_stats() -> Dict[str, Any]:
    """Per-device ``memory_stats()`` (HBM accounting on TPU backends),
    ``None`` per device where unsupported — CPU devices typically
    return nothing, and the bundle says so rather than omitting the
    section."""
    out: Dict[str, Any] = {}
    try:
        import jax
        for d in jax.devices():
            stats_fn = getattr(d, "memory_stats", None)
            try:
                out[str(d)] = stats_fn() if stats_fn is not None else None
            except Exception as e:
                out[str(d)] = {"error": f"{type(e).__name__}: {e}"}
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"
    return out


# -- the serve-state hookup -------------------------------------------------

# live ModelServers announce themselves so bundles can carry their
# queue state; weak references — the recorder must never keep a closed
# server alive
_SERVERS: "weakref.WeakSet" = weakref.WeakSet()


def register_server(server) -> None:
    """Called by ``ModelServer.__init__``; the bundle's ``serve``
    section is built from every still-alive registrant's
    ``telemetry_status()``."""
    _SERVERS.add(server)


def live_servers() -> List[Any]:
    return list(_SERVERS)


def _serve_status() -> List[dict]:
    out = []
    for server in live_servers():
        try:
            out.append(server.telemetry_status())
        except Exception as e:
            out.append({"error": f"{type(e).__name__}: {e}"})
    return out


def _slo_state() -> dict:
    """The SLO tracker's verdicts — was the process inside its error
    budgets when the bundle was cut; degrades like every probe."""
    try:
        from sparkdl_tpu.obs.slo import slo_tracker
        return slo_tracker().status()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _request_state() -> dict:
    """The request log's state plus the most recent per-request
    records (id, status, latency, phase breakdown) — the bundle's
    "which requests were in flight and where were they stuck"
    section. Bounded: last 32 records, the ring itself is already
    capped."""
    try:
        from sparkdl_tpu.obs.request_log import request_log
        rlog = request_log()
        recent = [{
            "request_id": r.request_id, "model": r.model,
            "rows": r.rows, "batches": r.batches, "status": r.status,
            "total_s": round(r.total_s, 6),
            "phases": {k: round(v, 6) for k, v in r.phases.items()},
        } for r in rlog.records()[-32:]]
        return {**rlog.status(), "recent": recent}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def resilience_state() -> dict:
    """The resilience layer's drill/recovery state — injection config
    + per-site counts (resilience/faults.py), every live session's
    circuit verdict, and the retry/shed totals — ONE shape shared by
    the flight bundle and ``/statusz`` (docs/RESILIENCE.md), so a curl
    and a postmortem never disagree; degrades like every probe."""
    try:
        from sparkdl_tpu.resilience import faults
        out: Dict[str, Any] = {"faults": faults.state()}
        snap = default_registry().snapshot()
        out["totals"] = {
            k: snap[k] for k in (
                "faults.injected", "serve.retries", "serve.shed",
                "serve.shed_rows", "serve.circuit_rejections",
                "engine.retries", "resilience.retries",
                "resilience.budget_denied") if k in snap}
        circuits: Dict[str, Any] = {}
        for server in live_servers():
            try:
                for name, sess in getattr(server, "_sessions",
                                          {}).items():
                    circuits[name] = sess.circuit.status()
            except Exception as e:
                circuits["error"] = f"{type(e).__name__}: {e}"
        out["circuits"] = circuits
        return out
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def ledger_state() -> dict:
    """The utilization ledger's live-roofline state — a fresh window
    when one is due, the current ceilings, and the bounded history
    ring (obs/ledger.py) — ONE shape shared by the flight bundle and
    ``/statusz`` so a curl and a postmortem never disagree; degrades
    like every probe."""
    try:
        from sparkdl_tpu.obs.ledger import ledger
        led = ledger()
        led.tick_due()
        return {**led.status(), "history": led.history()}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def compile_state() -> dict:
    """The compile log's forensics — per-function compile counts,
    retrace/unexpected verdicts, the last event (obs/compile_log.py)
    — ONE shape shared by the flight bundle and ``/statusz``;
    degrades like every probe. Recent
    events ride along (bounded: last 16) so a retrace-triggered dump
    carries the diff that caused it."""
    try:
        from sparkdl_tpu.obs.compile_log import compile_log
        log = compile_log()
        recent = [{
            "name": e.name, "kind": e.kind,
            "wall_s": round(e.wall_s, 4), "retrace": e.retrace,
            "unexpected": e.unexpected, "diff": e.diff,
            "flops": (e.cost or {}).get("flops"),
        } for e in log.events()[-16:]]
        return {**log.state(), "recent": recent}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def pipeline_state() -> dict:
    """The parallel host pipeline's live state — resolved
    mode/workers/read-ahead plus the ``pipeline.*`` counters
    (data/pipeline.py) — ONE shape shared by the flight bundle and
    ``/statusz``; degrades like every probe."""
    try:
        from sparkdl_tpu.data.pipeline import state
        return state()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def inputsvc_state() -> dict:
    """The disaggregated input service's live state — the last
    stream's resolved/live fleet plus the ``inputsvc.*`` counters
    (decode RPCs, failovers, snapshot hits/corruptions;
    sparkdl_tpu/inputsvc, docs/DATA_SERVICE.md) — ONE shape shared by
    the flight bundle and ``/statusz``; degrades like every probe."""
    try:
        from sparkdl_tpu.inputsvc.client import state
        return state()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def fleet_state() -> dict:
    """The fleet control plane's live state — every
    :class:`~sparkdl_tpu.fleet.registry.ModelRegistry` in this process
    (deployed models/versions, swap tallies, router replica map,
    warm-start cache hits/corruptions; sparkdl_tpu/fleet,
    docs/SERVING.md "Fleet control plane") — ONE shape shared by the
    flight bundle and ``/statusz``. A
    process that never imported the fleet package renders
    ``registries: []``; degrades like every probe."""
    try:
        import sys
        mod = sys.modules.get("sparkdl_tpu.fleet.registry")
        if mod is None:     # fleet never imported: nothing to report
            return {"registries": []}
        return {"registries": [r.state()
                               for r in mod.live_registries()]}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def workers_state() -> list:
    """The per-worker telemetry plane's ``workers[]`` section — agent
    state, last spans, counter snapshot, fault config for every
    pipeline worker process that has shipped a frame
    (obs/remote.py) — ONE shape shared by the flight bundle and
    ``/statusz`` so a curl and a postmortem never disagree (a
    worker-death bundle NAMES the dead worker here); degrades like
    every probe."""
    try:
        from sparkdl_tpu.obs import remote
        return remote.aggregator().workers_status()
    except Exception as e:
        return [{"error": f"{type(e).__name__}: {e}"}]


def _autotune_state() -> dict:
    """The autotune controller's knob/decision state — the bundle's
    "what was the loop doing" section; degrades like every other probe
    (lazy import: obs must stay import-light)."""
    try:
        from sparkdl_tpu.autotune.core import controller
        return controller().state()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


class FlightRecorder:
    """Retention + bundle writer (module docstring). One process-wide
    instance (:func:`recorder`); standalone instances exist for
    tests."""

    # sparkdl-lint H3 contract: dumps can fire concurrently (watchdog
    # thread, SIGUSR2 helper thread, the dispatcher's failure path) —
    # the dump bookkeeping holds self._lock
    _lock_guards = ("dumps", "last_dump_path")

    def __init__(self):
        self._armed_override: Optional[bool] = None
        self._lock = threading.Lock()
        self._seq = 0
        self.dumps = 0
        self.last_dump_path: Optional[str] = None
        self._signal_installed = False
        self._signal_degraded = False
        self._epoch = time.perf_counter()

    # -- arming --------------------------------------------------------------

    @property
    def armed(self) -> bool:
        ov = self._armed_override
        if ov is not None:
            return ov
        return _env_armed()

    def arm(self) -> None:
        """Arm retention + triggers: the tracer starts retaining spans
        (unless a programmatic disarm pinned it off) and SIGUSR2 gains
        a dump handler (probe-and-degrade: non-main-thread or
        signal-less platforms warn once and skip)."""
        self._armed_override = True
        trc = tracer()
        if not trc.armed:
            trc.arm()
        self._install_signal()

    def disarm(self) -> None:
        self._armed_override = False

    def _install_signal(self) -> None:
        if self._signal_installed or self._signal_degraded:
            return
        try:
            import signal

            def _on_sigusr2(signum, frame):
                # the dump runs on a helper thread: bundle building
                # takes registry/tracer locks, and a signal frame that
                # interrupted a lock holder must not self-deadlock
                threading.Thread(
                    target=self.dump, kwargs={"reason": "SIGUSR2"},
                    name="sparkdl-flight-sigusr2", daemon=True).start()

            signal.signal(signal.SIGUSR2, _on_sigusr2)
            self._signal_installed = True
        except (AttributeError, ValueError, OSError) as e:
            # AttributeError: no SIGUSR2 on this platform;
            # ValueError: not the main thread — degrade once, loudly
            self._signal_degraded = True
            logger.warning(
                "flight recorder: SIGUSR2 trigger unavailable (%s); "
                "dump() and the watchdog trigger still work", e)
            default_registry().counter("flight.degrade_events").add()

    # -- the bundle ----------------------------------------------------------

    def _next_path(self) -> str:
        with self._lock:
            self._seq += 1
            seq = self._seq
        return os.path.join(
            _bundle_dir(), f"sparkdl_flight_{os.getpid()}_{seq:03d}.json")

    def bundle(self, reason: str = "",
               extra: Optional[dict] = None) -> dict:
        """The forensics dict (what :meth:`dump` writes): every section
        degrades independently — a dump must never fail because one
        probe did."""
        trc = tracer()
        events = trc.trace_events()
        # refresh the hbm.* gauges so the registry snapshot below
        # carries the current high-watermarked HBM accounting, not a
        # stale window's (obs/compile_log.py; degrades internally)
        try:
            from sparkdl_tpu.obs.compile_log import publish_hbm
            publish_hbm()
        except Exception as e:
            default_registry().counter("flight.degrade_events").add()
            logger.debug("flight: hbm refresh failed (%s)", e)
        return {
            "schema": BUNDLE_SCHEMA,
            "reason": reason,
            "pid": os.getpid(),
            # wall-clock stamp so bundles order across processes; all
            # span/latency math stays on perf_counter (H5)
            "written_unix": time.time(),  # sparkdl-lint: allow[H5] -- forensics bundle timestamp, not span/latency math
            "uptime_s": round(time.perf_counter() - self._epoch, 3),
            "platform": platform_info(),
            "memory_stats": memory_stats(),
            "registry": default_registry().snapshot(),
            "watchdog": watchdog().verdict(),
            "spans": events,
            "span_count": sum(1 for e in events if e.get("ph") == "X"),
            "spans_dropped": trc.dropped,
            "serve": _serve_status(),
            "autotune": _autotune_state(),
            "compile": compile_state(),
            "ledger": ledger_state(),
            "pipeline": pipeline_state(),
            "inputsvc": inputsvc_state(),
            "fleet": fleet_state(),
            "workers": workers_state(),
            "slo": _slo_state(),
            "requests": _request_state(),
            "resilience": resilience_state(),
            "extra": extra or {},
        }

    def dump(self, path: Optional[str] = None, reason: str = "",
             extra: Optional[dict] = None) -> str:
        """Write one self-contained bundle; returns its path. Works
        armed or not (on-demand forensics are free to ask for), and is
        spanned on the ``obs`` lane so the postmortem's own cost shows
        up in the timeline it captured."""
        if path is None:
            path = self._next_path()
        with span("flight.dump", lane="obs", reason=reason[:80]):
            data = self.bundle(reason=reason, extra=extra)
            with open(path, "w", encoding="utf-8") as f:
                json.dump(data, f, default=str)
        with self._lock:
            self.dumps += 1
            self.last_dump_path = path
        default_registry().counter("flight.dumps").add()
        logger.warning(
            "flight recorder: bundle written to %s (%s; %d spans, "
            "%d registry keys)", path, reason or "explicit dump",
            data["span_count"], len(data["registry"]))
        return path

    def record_failure(self, exc: BaseException, where: str
                       ) -> Optional[str]:
        """The unhandled-failure trigger (serve dispatcher exception
        path): always counts ``flight.failures``; dumps only when
        armed — a disarmed process must not start writing files as a
        side effect of an error it already reports."""
        default_registry().counter("flight.failures").add()
        if not self.armed:
            return None
        try:
            return self.dump(
                reason=f"unhandled failure in {where}: "
                       f"{type(exc).__name__}: {exc}")
        except Exception:
            logger.exception(
                "flight recorder: failure dump failed (original "
                "failure in %s: %s)", where, exc)
            return None

    def status(self) -> dict:
        """The scrape-able state (``/statusz``)."""
        with self._lock:
            dumps = self.dumps
            last = self.last_dump_path
        return {"armed": self.armed, "dumps": dumps,
                "last_dump_path": last,
                "sigusr2": self._signal_installed}

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        # the lock is process-local and the signal handler/dump history
        # belong to the process that wrote them; armed-ness travels
        state = self.__dict__.copy()
        del state["_lock"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._signal_installed = False
        self._epoch = time.perf_counter()


_RECORDER = FlightRecorder()


def recorder() -> FlightRecorder:
    """THE process-wide flight recorder (dump triggers all feed it)."""
    return _RECORDER


def autoarm() -> bool:
    """Apply ``SPARKDL_TPU_FLIGHT=1``'s side effects (signal handler +
    span retention) if the env asks and nothing pinned the recorder
    off. Idempotent and cheap; called from the common entry point
    (``ModelServer.__init__``)."""
    rec = _RECORDER
    if rec._armed_override is None and _env_armed():
        rec.arm()
        return True
    return rec.armed


def record_failure(exc: BaseException, where: str) -> Optional[str]:
    """Module-level shorthand for ``recorder().record_failure(...)``."""
    return _RECORDER.record_failure(exc, where)
