"""Per-server serving counters, published into the obs registry.

The RunnerMetrics discipline, applied to the request axis: one
ServeMetrics object is shared by every submitter thread and the
dispatcher, every write holds the lock (sparkdl-lint H3), the lock
drops on the wire (StageMetrics precedent), and ``publish()`` renders
the cumulative values as idempotent ``serve.*`` gauges in a
:class:`~sparkdl_tpu.obs.registry.MetricsRegistry` — the server calls
it after every dispatch/rejection, so ``snapshot()`` readers always see current numbers without a second
bookkeeping path.

Latency is a :class:`~sparkdl_tpu.obs.registry.Reservoir` (bounded
sliding window, nearest-rank quantiles): p50/p99 are what the serving
contract is judged on, and neither a counter nor a gauge can carry a
quantile. Fill ratio is ``batch_rows / batch_capacity_rows`` — the
fraction of dispatched device-batch rows that held real requests; the
number dynamic micro-batching exists to maximize.
"""

from __future__ import annotations

import threading
from typing import Dict

from sparkdl_tpu.obs.registry import Reservoir


class ServeMetrics:
    """Thread-safe cumulative serving counters for one ModelServer."""

    # sparkdl-lint H3 contract: submitters and the dispatcher write
    # concurrently — every counter write holds self._lock
    _lock_guards = ("requests", "rows", "batches", "batch_rows",
                    "batch_capacity_rows", "rejections",
                    "deadline_misses", "failures", "retries", "shed",
                    "shed_rows", "circuit_rejections")

    def __init__(self):
        self.requests = 0
        self.rows = 0
        self.batches = 0
        self.batch_rows = 0
        self.batch_capacity_rows = 0
        self.rejections = 0
        self.deadline_misses = 0
        # dispatch-time failures (the model/runner raised): a separate
        # stream from deadline_misses, and — with them — the
        # availability population the SLO tracker judges. NEITHER ever
        # lands in the latency reservoir: percentiles are computed
        # over successful requests only, availability over the rest
        # (pinned by tests/test_request_obs.py).
        self.failures = 0
        # resilience counters (docs/RESILIENCE.md): granted micro-
        # batch re-dispatches; requests/rows shed by priority
        # displacement or the burn-driven admission gate; submissions
        # refused by an open circuit breaker
        self.retries = 0
        self.shed = 0
        self.shed_rows = 0
        self.circuit_rejections = 0
        self._latency = Reservoir("serve.latency_seconds")
        self._lock = threading.Lock()

    # -- recording -----------------------------------------------------------

    def add_request(self, rows: int) -> None:
        with self._lock:
            self.requests += 1
            self.rows += rows

    def add_rejection(self) -> None:
        with self._lock:
            self.rejections += 1

    def add_deadline_miss(self) -> None:
        with self._lock:
            self.deadline_misses += 1

    def add_failure(self) -> None:
        with self._lock:
            self.failures += 1

    def add_retry(self) -> None:
        with self._lock:
            self.retries += 1

    def add_shed(self, rows: int) -> None:
        with self._lock:
            self.shed += 1
            self.shed_rows += rows

    def add_circuit_rejection(self) -> None:
        with self._lock:
            self.circuit_rejections += 1

    def add_batch(self, valid_rows: int, capacity_rows: int) -> None:
        with self._lock:
            self.batches += 1
            self.batch_rows += valid_rows
            self.batch_capacity_rows += capacity_rows

    def observe_latency(self, seconds: float, exemplar=None) -> None:
        """One SUCCESSFUL request's latency; ``exemplar`` (armed runs)
        is the request_id + phase breakdown retained for the window's
        worst cases (Reservoir exemplars, obs/registry.py) so a
        scraped p99 resolves to an actual request."""
        self._latency.observe(seconds, exemplar=exemplar)

    def latency_exemplars(self) -> list:
        """The retained worst-case latency exemplars (largest first)."""
        return self._latency.exemplars()

    # -- readout -------------------------------------------------------------

    @property
    def batch_fill_ratio(self) -> float:
        """Mean fraction of dispatched device-batch rows that carried
        real request rows (the rest was pad); 0.0 before any batch."""
        with self._lock:
            if not self.batch_capacity_rows:
                return 0.0
            return self.batch_rows / self.batch_capacity_rows

    def latency_seconds(self, q: float) -> float:
        """Nearest-rank latency quantile over the retained window."""
        return self._latency.quantile(q)

    def as_dict(self) -> Dict[str, float]:
        """One flat dict (the deploy example's printout)."""
        with self._lock:
            vals = {"requests": self.requests, "rows": self.rows,
                    "batches": self.batches,
                    "rejections": self.rejections,
                    "deadline_misses": self.deadline_misses,
                    "failures": self.failures,
                    "retries": self.retries,
                    "shed": self.shed,
                    "shed_rows": self.shed_rows,
                    "circuit_rejections": self.circuit_rejections}
        vals["batch_fill_ratio"] = round(self.batch_fill_ratio, 4)
        p50, p99 = self._latency.quantiles((0.5, 0.99))
        vals["latency_p50_ms"] = round(p50 * 1e3, 3)
        vals["latency_p99_ms"] = round(p99 * 1e3, 3)
        vals["latency_exemplars_dropped"] = \
            self._latency.exemplars_dropped
        return vals

    def publish(self, registry) -> None:
        """Set this server's cumulative counters as ``serve.*`` gauges
        — idempotent (gauges, not counter adds), the
        RunnerMetrics.publish precedent. Live queue depth
        (``serve.queue_rows`` / ``serve.queue_rows_peak``) is set by
        the server hot path directly, not here."""
        with self._lock:
            vals = {"serve.requests": self.requests,
                    "serve.rows": self.rows,
                    "serve.batches": self.batches,
                    "serve.rejections": self.rejections,
                    "serve.deadline_misses": self.deadline_misses,
                    "serve.failures": self.failures,
                    "serve.retries": self.retries,
                    "serve.shed": self.shed,
                    "serve.shed_rows": self.shed_rows,
                    "serve.circuit_rejections": self.circuit_rejections}
        vals["serve.batch_fill_ratio"] = self.batch_fill_ratio
        p50, p99 = self._latency.quantiles((0.5, 0.99))
        vals["serve.latency_p50_ms"] = p50 * 1e3
        vals["serve.latency_p99_ms"] = p99 * 1e3
        vals["serve.latency_exemplars_dropped"] = \
            self._latency.exemplars_dropped
        for name, value in vals.items():
            registry.gauge(name).set(value)

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]      # the Reservoir carries its own hooks
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
