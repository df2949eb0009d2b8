"""Profiling and per-stage throughput metrics.

SURVEY §5 ("tracing/profiling: absent in the reference — add
jax.profiler trace + per-stage images/sec counters, needed to prove the
north-star number"). Two tools:

* :func:`trace` — context manager around ``jax.profiler`` producing a
  device trace (``.xplane.pb``: XLA ops and programs per device) with
  the program's own spans beside it on the same clock;
* :class:`StageMetrics` — cumulative wall-time/row counters per plan
  stage, collected by the engine when attached, so a pipeline run can
  report where its time went (decode vs resize vs device apply).

Both publish into the unified observability layer
(:mod:`sparkdl_tpu.obs`): ``StageMetrics.publish`` /
``RunnerMetrics.publish`` set registry gauges and
:func:`throughput_report` renders from the registry snapshot; for
TIMELINES (who waited on whom, one shared clock) arm
``SPARKDL_TPU_TRACE=1`` and see docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, Optional


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a device trace of the enclosed block into ``log_dir``,
    with the program's own spans beside it on the same clock.

    The profiler's host and Python tracers stay off: with the host
    tracer on, every host-to-device copy of a uint8 image batch writes
    some six million ``Transpose`` events (217 MB of trace and 2.4 s a
    step of 1,024 rows on the v5e, PERF.md section 6). The host side
    comes from :mod:`sparkdl_tpu.obs.trace` instead: the span tracer
    and the compile log are armed for the block (and put back as they
    were after it), and the spans are written to
    ``<log_dir>/program_spans.json`` as ``Tracer.export`` writes them.
    Two spans on the ``profiler`` lane, ``profiler.start_trace`` and
    ``profiler.stop_trace``, time the two calls; the first one's
    ``perf_counter`` attribute is the ``time.perf_counter()`` read at
    ``start_trace``, the zero of the ``.xplane.pb``'s clock, so a span
    lies at ``ts - <that span's ts>`` microseconds on the device
    trace."""
    import jax.profiler

    from sparkdl_tpu.obs import compile_log, tracer
    trc, log = tracer(), compile_log()
    was = (trc._override, log._override)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    os.makedirs(log_dir, exist_ok=True)
    trc.arm()
    log.arm()
    try:
        zero = time.perf_counter()
        jax.profiler.start_trace(log_dir, profiler_options=options)
        trc._record("profiler.start_trace", "profiler", zero,
                    time.perf_counter(), {"perf_counter": zero})
        try:
            yield
        finally:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            trc._record("profiler.stop_trace", "profiler", t,
                        time.perf_counter(), {})
            trc.export(os.path.join(log_dir, "program_spans.json"))
    finally:
        trc._override, log._override = was


@dataclass
class _StageStat:
    seconds: float = 0.0
    calls: int = 0
    rows: int = 0


@dataclass
class StageMetrics:
    """Thread-safe per-stage counters. Attach to a
    :class:`~sparkdl_tpu.data.engine.LocalEngine` via
    ``LocalEngine(stage_metrics=...)`` (or set ``engine.stage_metrics``)
    and run any DataFrame materialization."""

    _stats: Dict[str, _StageStat] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    # Locks don't pickle; an engine carrying metrics can ship inside a
    # stage closure (spark_binding) — drop the lock on the wire and
    # recreate on arrival, like RunnerMetrics. Counts collected on the
    # remote side stay remote (same boundary as RunnerMetrics: driver
    # metrics are a LocalEngine feature).
    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    def add(self, stage_name: str, seconds: float, rows: int):
        with self._lock:
            st = self._stats.setdefault(stage_name, _StageStat())
            st.seconds += seconds
            st.calls += 1
            st.rows += rows

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "seconds": st.seconds,
                    "calls": st.calls,
                    "rows": st.rows,
                    "rows_per_second": (st.rows / st.seconds
                                        if st.seconds else 0.0),
                }
                for name, st in self._stats.items()
            }

    def publish(self, registry) -> None:
        """Set the cumulative per-stage counters as
        ``engine.stage.<name>.<field>`` gauges in an
        :class:`~sparkdl_tpu.obs.registry.MetricsRegistry` —
        idempotent (gauges, not counter adds), so reports can publish
        on every render without double counting."""
        for name, st in self.as_dict().items():
            for field_name in ("seconds", "calls", "rows"):
                registry.gauge(
                    f"engine.stage.{name}.{field_name}"
                ).set(st[field_name])

    def report(self) -> str:
        """Human-readable table, slowest stage first."""
        return _format_stage_table(self.as_dict())


def _format_stage_table(stats: Dict[str, Dict[str, float]]) -> str:
    rows = sorted(stats.items(), key=lambda kv: -kv[1]["seconds"])
    if not rows:
        return "(no stages recorded)"
    width = max(len(n) for n, _ in rows)
    lines = [f"{'stage'.ljust(width)}  seconds  calls    rows   rows/s"]
    for name, st in rows:
        rps = (st["rows"] / st["seconds"] if st["seconds"] else 0.0)
        lines.append(
            f"{name.ljust(width)}  {st['seconds']:7.3f}  "
            f"{int(st['calls']):5d}  {int(st['rows']):6d}  "
            f"{rps:7.0f}")
    return "\n".join(lines)


def _stage_stats_from_snapshot(snap: Dict[str, float]
                               ) -> Dict[str, Dict[str, float]]:
    """Invert ``StageMetrics.publish``: ``engine.stage.<name>.<field>``
    snapshot keys back into per-stage stat dicts (stage names may
    themselves contain dots — the field is always the LAST segment)."""
    prefix = "engine.stage."
    stats: Dict[str, Dict[str, float]] = {}
    for key, value in snap.items():
        if not key.startswith(prefix):
            continue
        name, _, field_name = key[len(prefix):].rpartition(".")
        if name and field_name in ("seconds", "calls", "rows"):
            stats.setdefault(
                name, {"seconds": 0.0, "calls": 0, "rows": 0}
            )[field_name] = value
    return stats


def throughput_report(stage_metrics: Optional[StageMetrics] = None,
                      runner_metrics=None, registry=None) -> str:
    """Combined engine-stage + device-runner report, routed through the
    obs registry: both inputs publish into ``registry`` (a fresh
    :class:`~sparkdl_tpu.obs.registry.MetricsRegistry` when not given)
    and the text renders FROM its ``snapshot()``, so the printed
    numbers and the machine-readable ones can never diverge. The
    device line carries the host-copy proof counters
    (``bytes_staged`` / ``bytes_copied`` / ``transfer_wait_seconds``),
    not just throughput."""
    from sparkdl_tpu.obs import MetricsRegistry
    reg = registry if registry is not None else MetricsRegistry()
    if stage_metrics is not None:
        stage_metrics.publish(reg)
    if runner_metrics is not None:
        runner_metrics.publish(reg)
    snap = reg.snapshot()
    parts = []
    if stage_metrics is not None:
        # values come from the snapshot, but only for the stages THIS
        # StageMetrics holds — a reused registry (default_registry())
        # keeps gauges from earlier runs, and a report must not list a
        # stage the current run never executed
        current = set(stage_metrics.as_dict())
        stats = {name: st for name, st
                 in _stage_stats_from_snapshot(snap).items()
                 if name in current}
        parts.append(_format_stage_table(stats))
    if runner_metrics is not None:
        rows = snap.get("ship.rows", 0.0)
        secs = snap.get("ship.seconds", 0.0)
        rps = rows / secs if secs else 0.0
        parts.append(
            f"device: {int(rows)} rows in {secs:.3f}s = "
            f"{rps:.0f} rows/s "
            f"({int(snap.get('ship.batches', 0))} batches, "
            f"{int(snap.get('ship.bytes_staged', 0))} B staged, "
            f"{int(snap.get('ship.bytes_copied', 0))} B copied, "
            f"{snap.get('ship.transfer_wait_seconds', 0.0):.3f}s "
            "transfer wait)")
    if parts:
        # the bottleneck verdict, from THE one attribution code path
        # (obs/ledger.py — the same ledger.attribute() the live
        # ledger.bound_by gauge uses): the last closed window
        # when the ledger ran, else cumulative process totals
        from sparkdl_tpu.obs.ledger import ledger
        v = ledger().current_verdict()
        parts.append(f"bound by: {v['bound_by']} "
                     f"(headroom {v['headroom_pct']:.0f}%, "
                     f"{v['basis']})")
    return "\n".join(parts) if parts else "(no metrics)"
