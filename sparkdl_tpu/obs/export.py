"""Scrapeable health surface: Prometheus text + ``/healthz`` +
``/statusz`` from a localhost stdlib HTTP server.

The registry (PR 3) and the serve metrics (PR 4) made the pipeline's
numbers *recordable*; this module makes them *operable*: a CI soak, a
curl, or a Prometheus scraper can watch a live process without any
in-process hook. Three endpoints, one tiny threading HTTP server
(stdlib only — no new dependency, bound to localhost by default):

* ``/metricsz`` — ``MetricsRegistry`` rendered as Prometheus text
  exposition format (``# TYPE`` per metric; counters stay counters,
  gauges gauges, reservoirs flatten to ``_p50``/``_p99`` gauges plus a
  ``_count`` counter — same flattening as ``snapshot()``).
* ``/healthz`` — liveness (the server answering IS the liveness bit)
  plus the stall watchdog's verdict: 200 while healthy, 503 with the
  stalled sources named once the watchdog flags a wedge.
* ``/statusz`` — operator JSON: uptime, platform, watchdog verdict,
  flight-recorder state, and per-model serve state (warmup, queue
  depth, fill ratio) for every attached/registered ``ModelServer``.

Attach it to a server (``ModelServer.serve_telemetry(port=...)``) or
run it standalone around batch runs (:func:`start_telemetry`) — the
registry is process-wide either way, so a standalone endpoint still
sees every ship/collective/sanitize counter. ``port=0`` (the default)
lets the OS pick; read ``TelemetryServer.port`` after ``start()``.

Clocks are ``perf_counter`` deltas only (uptime) — sparkdl-lint H5.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from sparkdl_tpu.obs import flight as _flight
from sparkdl_tpu.obs.registry import (
    Counter,
    Gauge,
    MetricsRegistry,
    Reservoir,
    default_registry,
)
from sparkdl_tpu.obs.watchdog import watchdog

logger = logging.getLogger(__name__)

#: every exported sample is prefixed so a shared Prometheus namespace
#: can tell this process's pipeline metrics from anyone else's
PROM_PREFIX = "sparkdl_"

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def prom_name(name: str) -> str:
    """A registry key as a legal Prometheus metric name: dots (and any
    other illegal byte) become underscores, and the ``sparkdl_`` prefix
    guarantees a legal leading character."""
    return PROM_PREFIX + _PROM_BAD.sub("_", name)


#: ``# HELP`` text by registry-key prefix (longest prefix wins; the
#: registry's dotted ``<lane>.<what>`` convention makes the lane the
#: help unit — per-key prose lives in docs/OBSERVABILITY.md's table,
#: which sparkdl-lint H9 keeps in sync with the code)
HELP_BY_PREFIX = (
    ("ledger.util.", "live-roofline utilization fraction for this "
                     "pipeline lane, per ledger window (obs/ledger.py)"),
    ("ledger.", "windowed utilization-ledger accounting — the live "
                "bottleneck verdict and its bookkeeping (obs/ledger.py)"),
    ("ship.", "host->device ship path: dispatch queue, staging copies, "
              "transfer waits (runtime/runner.py)"),
    ("engine.stage.", "per-stage engine counters published from "
                      "StageMetrics (utils/profiling.py)"),
    ("engine.", "host execution engine: stage busy time and retries "
                "(data/engine.py)"),
    ("pipeline.", "parallel host pipeline: pooled decode workers, "
                  "ordered re-merge, shared-memory hand-off "
                  "(data/pipeline.py)"),
    ("device.", "device-side accounting observed from the host "
                "(runtime/runner.py)"),
    ("serve.", "online serving front-end: admission, micro-batching, "
               "latency (sparkdl_tpu/serve)"),
    ("collective.", "mesh-program collective launch discipline "
                    "(parallel/mesh.py)"),
    ("sanitize.", "runtime transfer-guard sanitizer "
                  "(runtime/sanitize.py)"),
    ("autotune.", "closed-loop infeed autotuner (sparkdl_tpu/autotune)"),
    ("watchdog.", "stall watchdog verdicts (obs/watchdog.py)"),
    ("flight.", "flight-recorder forensics bundles (obs/flight.py)"),
    ("slo.", "rolling-window SLO burn-rate/budget verdicts "
             "(obs/slo.py)"),
    ("compile.", "compile forensics: jit compiles, retrace "
                 "attribution, the steady-state zero-retrace "
                 "guarantee (obs/compile_log.py)"),
    ("hbm.", "per-device memory_stats() HBM accounting with "
             "high-watermark tracking (obs/compile_log.py)"),
    ("obs.", "the observability layer's own accounting "
             "(sparkdl_tpu/obs)"),
    ("worker.", "cross-process telemetry shipped by pipeline worker "
                "processes: per-worker (worker.<i>.*) and rollup "
                "(worker.all.*) mirrors of worker-side counters, plus "
                "the aggregator's own accounting (obs/remote.py)"),
    ("faults.", "armed fault-injection drill counters "
                "(resilience/faults.py)"),
    ("resilience.", "shared retry-policy/budget accounting "
                    "(resilience/policy.py)"),
    ("telemetry.", "telemetry-endpoint handler failures "
                   "(obs/export.py)"),
)

_HELP_FALLBACK = ("sparkdl_tpu pipeline metric (registry key table: "
                  "docs/OBSERVABILITY.md)")


def prom_help(name: str) -> str:
    """The ``# HELP`` text for a registry key: longest matching lane
    prefix, with a generic fallback — every exported sample gets a
    HELP line (the Prometheus exposition contract ci.sh validates
    line-by-line), never a bare TYPE."""
    for prefix, text in HELP_BY_PREFIX:
        if name.startswith(prefix):
            return f"{text} [key: {name}]"
    return f"{_HELP_FALLBACK} [key: {name}]"


def _fmt(value: float) -> str:
    # Prometheus floats: repr round-trips, integers stay readable
    f = float(value)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def render_prometheus(registry: Optional[MetricsRegistry] = None) -> str:
    """The registry in Prometheus text exposition format (version
    0.0.4): one ``# HELP`` + ``# TYPE`` pair per metric, kinds
    preserved. This is THE scrape payload — ``tools/ci.sh``'s
    telemetry gate parses it line-by-line (every TYPE must follow its
    HELP) so a rendering regression fails the build, not the
    operator's dashboard."""
    registry = registry if registry is not None else default_registry()
    lines = []

    def emit(base: str, kind: str, value: float, key: str) -> None:
        lines.append(f"# HELP {base} {prom_help(key)}")
        lines.append(f"# TYPE {base} {kind}")
        lines.append(f"{base} {_fmt(value)}")

    for m in registry.metrics():
        base = prom_name(m.name)
        if isinstance(m, Counter):
            emit(base, "counter", m.value, m.name)
        elif isinstance(m, Gauge):
            emit(base, "gauge", m.value, m.name)
        elif isinstance(m, Reservoir):
            p50, p99 = m.quantiles((0.5, 0.99))
            emit(f"{base}_count", "counter", m.count, m.name)
            emit(f"{base}_p50", "gauge", p50, m.name)
            emit(f"{base}_p99", "gauge", p99, m.name)
    return "\n".join(lines) + "\n"


class TelemetryServer:
    """Localhost HTTP surface over the process-wide registry, watchdog,
    and flight recorder (module docstring).

    ``model_server`` (optional) scopes ``/statusz``'s serve section to
    one :class:`~sparkdl_tpu.serve.server.ModelServer`; without it the
    section covers every live server the flight recorder knows about.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 port: int = 0, host: str = "127.0.0.1",
                 model_server=None, watchdog_instance=None):
        self._registry = (registry if registry is not None
                          else default_registry())
        self._requested = (host, port)
        self._model_server = model_server
        self._watchdog = (watchdog_instance if watchdog_instance
                          is not None else watchdog())
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._epoch = time.perf_counter()

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "TelemetryServer":
        if self._httpd is not None:
            return self
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            server_version = "sparkdl-telemetry/1"

            def do_GET(self):  # noqa: N802 (stdlib contract)
                outer._route(self)

            def log_message(self, fmt, *args):
                logger.debug("telemetry: %s", fmt % args)

        self._httpd = ThreadingHTTPServer(self._requested, _Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="sparkdl-telemetry", daemon=True)
        self._thread.start()
        logger.info("telemetry endpoint listening on http://%s:%d "
                    "(/metricsz /healthz /statusz)", *self.address)
        return self

    @property
    def address(self):
        if self._httpd is None:
            return self._requested
        return self._httpd.server_address[:2]

    @property
    def port(self) -> int:
        return self.address[1]

    def url(self, path: str = "") -> str:
        host, port = self.address
        return f"http://{host}:{port}{path}"

    def close(self) -> None:
        httpd, self._httpd = self._httpd, None
        thread, self._thread = self._thread, None
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if thread is not None:
            thread.join(timeout=2.0)

    def __enter__(self) -> "TelemetryServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    # -- routing -------------------------------------------------------------

    def _route(self, handler: BaseHTTPRequestHandler) -> None:
        path = handler.path.split("?", 1)[0]
        try:
            if path in ("/metricsz", "/metrics"):
                # refresh the SLO gauges at scrape time: the serve
                # loop's publish is rate-limited (obs/slo.py
                # publish_due — status() scans the whole outcome
                # window, not a per-micro-batch cost), and the scrape
                # is exactly the rare reader that should pay for
                # freshness. Degrades silently: a broken tracker must
                # not 500 every other metric.
                try:
                    from sparkdl_tpu.obs.slo import slo_tracker
                    slo_tracker().publish(self._registry)
                except Exception as e:
                    self._registry.counter("telemetry.errors").add()
                    logger.debug("telemetry: slo refresh failed: %s",
                                 e)
                # the utilization ledger's reader-driven window: a
                # scrape closes a window when one is due, so
                # ledger.util.* is fresh without any in-process
                # arming; degrades like the SLO refresh (a broken
                # probe must not 500 every other metric)
                try:
                    from sparkdl_tpu.obs.ledger import ledger
                    ledger().tick_due()
                except Exception as e:
                    self._registry.counter("telemetry.errors").add()
                    logger.debug("telemetry: ledger tick failed: %s",
                                 e)
                # HBM accounting at scrape time: a scrape is exactly
                # the reader that should pay for gauge freshness (the
                # SLO-refresh precedent); degrades internally
                try:
                    from sparkdl_tpu.obs.compile_log import publish_hbm
                    publish_hbm(self._registry)
                except Exception as e:
                    self._registry.counter("telemetry.errors").add()
                    logger.debug("telemetry: hbm refresh failed: %s",
                                 e)
                body = render_prometheus(self._registry).encode()
                self._reply(handler, 200, body,
                            "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                verdict = self._watchdog.verdict()
                # a pipeline worker's OWN watchdog verdict reaches the
                # liveness bit too (obs/remote.py): a wedged worker
                # process is a stalled pipeline even when every
                # parent-side loop still beats
                try:
                    from sparkdl_tpu.obs import remote
                    worker_health = remote.aggregator().health()
                except Exception as e:
                    logger.debug("telemetry: worker health probe "
                                 "failed: %s", e)
                    worker_health = {"workers": 0, "stalled": [],
                                     "dead": []}
                code = (200 if verdict["healthy"]
                        and not worker_health["stalled"] else 503)
                # the compile-forensics detail (obs/compile_log.py):
                # unexpected retraces are a perf-guarantee violation,
                # not a liveness failure — the status code stays the
                # stall verdicts'; the detail flips so a probe (and
                # ci.sh's gate) sees the warm-start contract break
                try:
                    from sparkdl_tpu.obs.compile_log import compile_log
                    retraces = compile_log().unexpected_retraces
                except Exception:
                    retraces = None
                body = json.dumps({
                    "status": "ok" if code == 200 else "stalled",
                    "stalled_sources": verdict["stalled_sources"],
                    "worker_stalled": worker_health["stalled"],
                    "worker_dead": worker_health["dead"],
                    "watchdog_armed": verdict["armed"],
                    "unexpected_retraces": retraces,
                    "compile_steady": (retraces == 0
                                       if retraces is not None
                                       else None),
                }).encode()
                self._reply(handler, code, body, "application/json")
            elif path == "/statusz":
                body = json.dumps(self._statusz(),
                                  default=str).encode()
                self._reply(handler, 200, body, "application/json")
            else:
                self._reply(handler, 404,
                            b'{"error": "unknown path; try /metricsz, '
                            b'/healthz, /statusz"}',
                            "application/json")
        except Exception:
            # the health surface must never take the process down (and
            # a broken probe should read as a 500, not a hang) — but a
            # failing surface must COUNT its failures where the next
            # successful scrape sees them (H12)
            self._registry.counter("telemetry.errors").add()
            logger.exception("telemetry: %s handler failed", path)
            try:
                self._reply(handler, 500, b'{"error": "internal"}',
                            "application/json")
            # sparkdl-lint: allow[H12] -- root failure counted in telemetry.errors above; the reply failing means the peer hung up, and there is no socket left to account anything to
            except Exception as e:
                logger.debug("telemetry: error reply failed: %s", e)

    @staticmethod
    def _reply(handler, code: int, body: bytes, ctype: str) -> None:
        handler.send_response(code)
        handler.send_header("Content-Type", ctype)
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)

    def _statusz(self) -> dict:
        if self._model_server is not None:
            servers = [self._model_server.telemetry_status()]
        else:
            # the flight recorder's per-server degrade shaping, reused:
            # /statusz and flight bundles must not drift apart
            servers = _flight._serve_status()
        from sparkdl_tpu.obs.request_log import request_log
        from sparkdl_tpu.obs.slo import slo_tracker
        return {
            "pid": os.getpid(),
            "uptime_s": round(time.perf_counter() - self._epoch, 3),
            "platform": _flight.platform_info(),
            "watchdog": self._watchdog.verdict(),
            "flight": _flight.recorder().status(),
            # error budgets + burn rate (obs/slo.py) and the bounded
            # per-request log's state (obs/request_log.py) — the same
            # shapes the flight bundle carries, so a curl and a
            # postmortem never disagree
            "slo": slo_tracker().status(),
            "request_log": request_log().status(),
            # the resilience layer's drill/recovery state: fault-
            # injection config + per-site counts, live circuit
            # verdicts, retry/shed totals (docs/RESILIENCE.md) — same
            # shape as the flight bundle's section, so a curl and a
            # postmortem never disagree
            "resilience": _flight.resilience_state(),
            # the live roofline: current window, ceilings, and the
            # bounded history ring (obs/ledger.py) — literally the
            # same renderer the flight bundle uses
            "ledger": _flight.ledger_state(),
            # the parallel host pipeline's live worker/read-ahead/mode
            # picture (data/pipeline.py) — same shape as the flight
            # bundle's section, so a curl and a postmortem never
            # disagree
            "pipeline": _flight.pipeline_state(),
            # the disaggregated input service's fleet/snapshot picture
            # (sparkdl_tpu/inputsvc, docs/DATA_SERVICE.md) — same
            # shape as the flight bundle's section
            "inputsvc": _flight.inputsvc_state(),
            # the fleet control plane's deployments/swap/warm-start
            # picture (sparkdl_tpu/fleet, docs/SERVING.md "Fleet
            # control plane") — same shape as the flight bundle's
            # section, so a curl and a postmortem never disagree
            "fleet": _flight.fleet_state(),
            # the cross-process telemetry plane's per-worker view
            # (obs/remote.py) — same shape as the flight bundle's
            # workers[] section, so a curl and a postmortem never
            # disagree
            "workers": _flight.workers_state(),
            # compile forensics (obs/compile_log.py): per-function
            # compile counts, retrace attribution, the steady-state
            # zero-retrace verdict — same shape as the flight
            # bundle's section ("diagnosing a compile storm",
            # docs/SERVING.md)
            "compile": _flight.compile_state(),
            "servers": servers,
            "metrics_count": len(self._registry.snapshot()),
        }


def start_telemetry(port: int = 0, host: str = "127.0.0.1",
                    registry: Optional[MetricsRegistry] = None
                    ) -> TelemetryServer:
    """Standalone endpoint around batch runs: start scraping the
    process-wide registry/watchdog/flight state with one call (close
    the returned server when done, or let the daemon thread die with
    the process)."""
    return TelemetryServer(registry=registry, port=port,
                           host=host).start()
