"""Unified pipeline observability: spans, a metrics registry,
Perfetto-exportable timelines, and an operable health surface across
engine → ship → device.

Eleven pieces (docs/OBSERVABILITY.md):

* :mod:`sparkdl_tpu.obs.compile_log` — compile forensics: every
  package jit compile routes through ONE CompileLog (callable name,
  abstract arg signature, wall time, ``cost_analysis``/
  ``memory_analysis`` FLOPs+bytes), recompiles of known functions
  carry a signature diff naming the offending argument, and
  ``warmup``/``prewarm`` mark programs *steady* — after which any
  compile counts ``compile.unexpected_retraces`` and fires a flight
  dump (the runtime-enforced zero-retrace guarantee); per-device
  ``memory_stats()`` publishes as periodic ``hbm.*`` gauges with
  high-watermark tracking;

* :mod:`sparkdl_tpu.obs.ledger` — the windowed utilization ledger:
  per-window rates over the hot paths' feed counters, divided by
  probed per-host ceilings into ``ledger.util.*`` fractions and ONE
  continuous ``ledger.bound_by`` roofline verdict (the same
  ``attribute()`` ``throughput_report`` prints);

* :mod:`sparkdl_tpu.obs.trace` — ``span(name, lane=...)`` recording
  into one process-wide bounded ring buffer on a single clock, armed by
  ``SPARKDL_TPU_TRACE=1`` (near-zero overhead disarmed), exported as
  Chrome/Perfetto trace-event JSON;
* :mod:`sparkdl_tpu.obs.registry` — named counters/gauges/reservoirs
  with ONE ``snapshot() -> dict`` (throughput_report, ``/metricsz``);
* :mod:`sparkdl_tpu.obs.report` — ``python -m sparkdl_tpu.obs report
  <trace.json>``: per-lane busy %, top spans, stall breakdown;
* :mod:`sparkdl_tpu.obs.watchdog` — heartbeat-fed stall detection for
  the hot loops (``SPARKDL_TPU_WATCHDOG=1``): no-progress beyond the
  threshold logs loudly, counts ``watchdog.stalls``, and dumps the
  flight recorder;
* :mod:`sparkdl_tpu.obs.flight` — the flight recorder
  (``SPARKDL_TPU_FLIGHT=1``): retains recent spans + the rolling
  registry, writes a self-contained forensics bundle on ``dump()``,
  SIGUSR2, serve dispatch failure, or a watchdog stall;
* :mod:`sparkdl_tpu.obs.export` — Prometheus text rendering plus a
  localhost ``/metricsz`` / ``/healthz`` / ``/statusz`` HTTP surface
  (stdlib only), attachable to a ``ModelServer`` or standalone;
* :mod:`sparkdl_tpu.obs.request_log` — per-request timelines: every
  serve submit mints a ``request_id``, armed requests record a phase
  breakdown (queue / coalesce / staging / device / reassembly) into a
  bounded ring, render as linked Perfetto flows, and feed the latency
  reservoir's worst-case exemplars (``report --tails`` attributes the
  p99 from an exported trace);
* :mod:`sparkdl_tpu.obs.slo` — rolling-window SLO evaluation (latency
  + availability objectives): error-budget remaining and burn rate,
  published as ``sparkdl_slo_*`` on ``/metricsz``;
* :mod:`sparkdl_tpu.obs.remote` — the cross-process telemetry plane:
  pipeline worker processes arm a :class:`TelemetryAgent` that ships
  spans, counter deltas, watchdog verdicts, degrade events, and fault
  state back over the result hand-off; the parent
  :class:`TelemetryAggregator` merges worker spans into ONE
  clock-aligned Perfetto trace, folds counters into ``worker.<i>.*``
  (+ ``worker.all.*`` rollups), and extends ``/healthz``, flight
  bundles (``workers[]``), and ``report --workers`` across process
  boundaries.

Import-light on purpose: nothing here pulls jax (the report CLI and
the telemetry endpoint work on any machine); :func:`timed_device_get`
and the flight recorder's platform probes import it lazily.
"""

from sparkdl_tpu.obs.compile_log import (
    CompileLog,
    compile_log,
    publish_hbm,
)
from sparkdl_tpu.obs.export import (
    TelemetryServer,
    render_prometheus,
    start_telemetry,
)
from sparkdl_tpu.obs.flight import FlightRecorder
from sparkdl_tpu.obs.flight import recorder as flight_recorder
from sparkdl_tpu.obs.ledger import (
    UtilizationLedger,
    ledger,
    ledger_poll,
    probe_ceilings,
)
from sparkdl_tpu.obs.ledger import attribute as ledger_attribute
from sparkdl_tpu.obs.registry import (
    Counter,
    Gauge,
    MetricsRegistry,
    Reservoir,
    default_registry,
)
from sparkdl_tpu.obs.request_log import (
    RequestLog,
    RequestRecord,
    RequestTimeline,
    request_log,
)
from sparkdl_tpu.obs.remote import (
    TelemetryAgent,
    TelemetryAggregator,
    telemetry_config,
)
from sparkdl_tpu.obs.remote import aggregator as telemetry_aggregator
from sparkdl_tpu.obs.slo import SLObjective, SLOTracker, slo_tracker
from sparkdl_tpu.obs.trace import (
    SpanRecord,
    Tracer,
    span,
    timed_device_get,
    tracer,
)
from sparkdl_tpu.obs.watchdog import StallWatchdog
from sparkdl_tpu.obs.watchdog import watchdog as stall_watchdog

__all__ = [
    "CompileLog",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "MetricsRegistry",
    "RequestLog",
    "RequestRecord",
    "RequestTimeline",
    "Reservoir",
    "SLObjective",
    "SLOTracker",
    "SpanRecord",
    "StallWatchdog",
    "TelemetryAgent",
    "TelemetryAggregator",
    "TelemetryServer",
    "Tracer",
    "UtilizationLedger",
    "compile_log",
    "default_registry",
    "flight_recorder",
    "ledger",
    "ledger_attribute",
    "ledger_poll",
    "probe_ceilings",
    "publish_hbm",
    "render_prometheus",
    "request_log",
    "slo_tracker",
    "span",
    "stall_watchdog",
    "start_telemetry",
    "telemetry_aggregator",
    "telemetry_config",
    "timed_device_get",
    "tracer",
]
