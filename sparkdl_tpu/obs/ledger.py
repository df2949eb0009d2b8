"""Windowed utilization ledger: a live roofline over the pipeline's
own counters, and THE bottleneck verdict operators read.

The only bottleneck diagnosis the system once had was an offline,
once-per-round verdict. Nothing live could say which ceiling (decode, link, compute,
serve coalesce) binds *right now* or how much headroom remains. The
tf.data paper (PAPERS.md, arxiv 2101.12127) makes the case directly:
input-pipeline bottleneck attribution must be a continuous runtime
signal, because it is what drives both autotuning and operator action.

The ledger turns the counters the hot paths already feed into
per-window *rates* and utilization fractions against measured
per-host ceilings:

* **feeds** (always-on monotonic counters, recorded by the hot paths
  themselves — the registry's one-sink discipline):
  ``engine.busy_seconds`` (host decode/stage busy time, LocalEngine),
  ``device.run_seconds`` (runner dispatch+drain wall),
  ``ship.bytes_shipped`` (input bytes handed to device dispatch),
  ``ship.transfer_wait_seconds_total`` (device_get drain waits),
  ``serve.coalesce_wait_seconds`` (the micro-batcher's fill window);
* **windows** (default 2 s, ``SPARKDL_TPU_LEDGER_WINDOW_S``,
  typo-degrade): each :meth:`UtilizationLedger.tick` snapshots the
  feeds, deltas them against the previous window, and divides:
  time-shaped lanes (decode / compute / serve) become busy fractions
  of the window wall; the link lane becomes measured bytes/s over the
  probed host↔device bandwidth, degrading to the transfer-wait
  fraction when no probe is available (``link_basis`` says which);
* **ceilings** (:func:`probe_ceilings`): one-shot ``measure_link``
  (``utils/measure``, which ``chip_smoke.py`` reads too), cached to ``SPARKDL_TPU_LEDGER_PROBE_FILE`` so a
  steady-state process never re-pays the probe; a corrupt or missing
  cache degrades to a fresh probe (counted, never silent). Probing is
  always DELIBERATE (an explicit call, or a caller injecting its own
  measurement): a tick reads memory or the cache file only — a
  scrape or flight dump on a wedged device must never block on a
  device probe;
* **verdict** (:func:`attribute`): ``bound_by`` = the max-utilization
  stage, ``headroom_pct`` = what remains under its ceiling. ONE code
  path: ``throughput_report`` and the live ``ledger.bound_by`` gauge
  are both this function, so the two verdicts cannot drift onto
  different math.

Published per window (registry gauges → ``/metricsz``):
``ledger.util.{decode,link,compute,serve}``, ``ledger.bound_by``
(:data:`STAGE_CODES` — Prometheus gauges are numbers; the string
verdict rides ``/statusz`` and flight bundles), and
``ledger.headroom_pct``; plus counters ``ledger.windows``,
``ledger.windows_evicted`` (ring evictions — bounded, never silent)
and ``ledger.counter_resets`` (a feed counter that moved backwards —
registry cleared/re-created — reads as an empty delta, not a negative
rate).

Arming (``SPARKDL_TPU_LEDGER=1`` or ``ledger().arm()``): the hot-path
:func:`ledger_poll` (runner.run epilogue, serve dispatcher — the
``autotune.poll`` precedent) advances windows under live traffic.
Reader-driven windows need no arming at all: ``/metricsz`` /
``/statusz`` scrapes and flight-bundle dumps call :meth:`tick_due`,
so any scrape gets a fresh window. Disarmed, ``ledger_poll`` is one
armed-check — the tracer's shared-no-op regime, pinned <10 µs in
``tests/test_ledger.py``.

Pickle discipline (StageMetrics precedent): the lock and the history
ring drop on the wire — windows measured in one process are that
process's record; configuration (window length, probed ceilings,
armed-ness) travels.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Mapping, Optional

from sparkdl_tpu.obs.registry import default_registry

logger = logging.getLogger(__name__)

_TRUE = ("1", "true", "yes", "on")

#: the four roofline lanes, in doc/report order
STAGES = ("decode", "link", "compute", "serve")

#: ``ledger.bound_by`` gauge coding (gauges are numbers; the string
#: verdict rides /statusz and flight bundles)
STAGE_CODES = {"idle": -1, "decode": 0, "link": 1, "compute": 2,
               "serve": 3}

#: feed counters, stage → registry key (the hot paths record these)
FEEDS = {
    "decode": "engine.busy_seconds",
    "compute": "device.run_seconds",
    "serve": "serve.coalesce_wait_seconds",
}
LINK_WAIT_FEED = "ship.transfer_wait_seconds_total"
#: link traffic: the input bytes each run handed to device dispatch
#: (runtime/runner.py record_run_feeds)
LINK_BYTES_FEED = "ship.bytes_shipped"
#: executed-FLOPs feed (runtime/runner.py record_run_feeds, populated
#: when the compile log recorded the program's cost_analysis) — lifts
#: the compute lane from a generic busy fraction to a model-specific
#: roofline when a device_gflops ceiling exists (compute_basis names
#: which)
COMPUTE_FLOPS_FEED = "device.flops_total"

#: default window length (seconds) when SPARKDL_TPU_LEDGER_WINDOW_S
#: is unset — long enough to smooth per-batch jitter, short enough
#: that an operator watching /metricsz sees the pipeline move
DEFAULT_WINDOW_S = 2.0

#: default history-ring capacity (windows) when
#: SPARKDL_TPU_LEDGER_HISTORY is unset — a few minutes of 2 s windows
DEFAULT_HISTORY = 64

#: bytes the one-shot link probe ships (small on purpose: the probe is
#: a ceiling estimate, not a benchmark)
PROBE_MB = 4

#: probe-cache schema tag — bump when the layout changes incompatibly
PROBE_SCHEMA = "sparkdl-ledger-probe/1"

_MB = 1024.0 * 1024.0


def _env_float(name: str, default: float) -> float:
    """Parse a positive float env var, typo-degrading to the default
    with one warning (the SPARKDL_TPU_TRACE_BUFFER precedent: a config
    typo must not make the module unusable)."""
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        val = float(raw)
        if val <= 0:
            raise ValueError(val)
        return val
    except ValueError:
        logger.warning("%s=%r is not a positive number; using the "
                       "default %s", name, raw, default)
        default_registry().counter("ledger.config_errors").add()
        return default


def _env_int(name: str, default: int) -> int:
    """Parse a positive int env var with the same typo-degrade
    contract as :func:`_env_float` — the module-level singleton parses
    these at import time, so a fractional or garbage value must warn
    and default, never make ``import sparkdl_tpu`` fail."""
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        val = int(raw)
        if val < 1:
            raise ValueError(val)
        return val
    except ValueError:
        logger.warning("%s=%r is not a positive int; using the "
                       "default %s", name, raw, default)
        default_registry().counter("ledger.config_errors").add()
        return default


def _env_armed() -> bool:
    return os.environ.get("SPARKDL_TPU_LEDGER", "").lower() in _TRUE


def attribute(util: Mapping[str, float]) -> Dict[str, Any]:
    """THE bottleneck verdict over per-stage utilization fractions —
    the one code path ``throughput_report`` and the live
    ``ledger.bound_by`` gauge both call, so the two verdicts cannot
    drift.

    ``bound_by`` is the max-utilization stage (ties break
    alphabetically-first, deterministically); ``headroom_pct`` is what
    remains under that stage's ceiling, floored at 0 (a value measured
    above its ceiling — the link moved between measurements — reads as
    zero headroom, not negative). An empty or all-zero ``util`` is an
    idle window: ``bound_by="idle"``, full headroom."""
    items = sorted(((k, float(v)) for k, v in util.items()),
                   key=lambda kv: (-kv[1], kv[0]))
    if not items or items[0][1] <= 0.0:
        return {"bound_by": "idle", "headroom_pct": 100.0,
                "util": {k: round(float(v), 4) for k, v in util.items()}}
    name, frac = items[0]
    return {"bound_by": name,
            "headroom_pct": round(max(0.0, (1.0 - frac) * 100.0), 1),
            "util": {k: round(float(v), 4) for k, v in util.items()}}


def _default_probe_file() -> str:
    env = os.environ.get("SPARKDL_TPU_LEDGER_PROBE_FILE", "")
    if env:
        return env
    import tempfile
    return os.path.join(tempfile.gettempdir(),
                        "sparkdl_tpu_ledger_probe.json")


def _valid_probe(data: Any) -> bool:
    return (isinstance(data, dict)
            and data.get("schema") == PROBE_SCHEMA
            and isinstance(data.get("link_h2d_MBps"), (int, float))
            and data["link_h2d_MBps"] > 0)


def probe_ceilings(path: Optional[str] = None, force: bool = False,
                   measure=None) -> Dict[str, Any]:
    """The per-host ceilings the ledger divides by: host↔device link
    bandwidth from a one-shot :func:`~sparkdl_tpu.utils.measure.measure_link`,
    cached to ``path`` (default ``SPARKDL_TPU_LEDGER_PROBE_FILE``) so
    steady state never re-pays the probe.

    Degrade ladder, every rung counted (``ledger.probe_errors``) and
    none silent: a corrupt/missing/stale-schema cache file → fresh
    probe (rewriting the cache); a failing probe (no backend) →
    ``{"error": ...}`` — the ledger then falls back to transfer-wait
    attribution for the link lane; a cache that cannot be written →
    the fresh probe is still returned."""
    path = path if path is not None else _default_probe_file()
    # a missing cache is the normal first run (probe below); an
    # existing-but-unusable one is a degrade, counted and re-probed
    if not force and os.path.exists(path):
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
            if _valid_probe(data):
                return data
            logger.warning("ledger: probe cache %s is invalid; "
                           "re-probing", path)
            default_registry().counter("ledger.probe_errors").add()
        except (OSError, json.JSONDecodeError) as e:
            logger.warning("ledger: probe cache %s unreadable (%s); "
                           "re-probing", path, e)
            default_registry().counter("ledger.probe_errors").add()
    if measure is None:
        from sparkdl_tpu.utils.measure import measure_link
        measure = measure_link
    try:
        link = measure(PROBE_MB)
    except Exception as e:
        default_registry().counter("ledger.probe_errors").add()
        logger.warning("ledger: link probe failed (%s); the link lane "
                       "degrades to transfer-wait attribution", e)
        return {"schema": PROBE_SCHEMA, "error": f"{type(e).__name__}: {e}"}
    probe = {
        "schema": PROBE_SCHEMA,
        "link_h2d_MBps": float(link["h2d_MBps"]),
        "link_d2h_MBps": float(link.get("d2h_MBps", 0.0)),
        "probe_mb": PROBE_MB,
        "source": "probe_ceilings",
        # wall-clock stamp so an operator can judge the cache's age
        # across restarts; window math stays on perf_counter (H5)
        "probed_unix": time.time(),  # sparkdl-lint: allow[H5] -- probe-cache freshness stamp for operators, not span/latency math
    }
    try:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(probe, f)
    except OSError as e:
        default_registry().counter("ledger.probe_errors").add()
        logger.warning("ledger: cannot write probe cache %s (%s); "
                       "this process keeps the probe in memory", path, e)
    return probe


class UtilizationLedger:
    """Windowed roofline accounting over the feed counters (module
    docstring). One process-wide instance (:func:`ledger`); standalone
    instances exist for tests."""

    # sparkdl-lint H3 contract: ticks can race (hot-path poll vs a
    # scrape vs a flight dump) — the window baseline and ring
    # bookkeeping hold self._lock
    _lock_guards = ("windows", "evicted")

    def __init__(self, window_s: Optional[float] = None,
                 history: Optional[int] = None,
                 probe_file: Optional[str] = None):
        self.window_s = (window_s if window_s is not None
                         else _env_float("SPARKDL_TPU_LEDGER_WINDOW_S",
                                         DEFAULT_WINDOW_S))
        if self.window_s <= 0:
            raise ValueError(
                f"window_s must be positive, got {self.window_s}")
        cap = (history if history is not None
               else _env_int("SPARKDL_TPU_LEDGER_HISTORY",
                             DEFAULT_HISTORY))
        if cap <= 0:
            raise ValueError(f"history must be positive, got {cap}")
        self.history_capacity = cap
        self.probe_file = probe_file
        # None → follow the env; True/False → programmatic override
        self._override: Optional[bool] = None
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=cap)
        self.windows = 0            # lifetime ticks that produced one
        self.evicted = 0            # ring evictions — never silent
        self._last_t: Optional[float] = None
        self._last: Optional[Dict[str, float]] = None
        self._ceilings: Optional[Dict[str, Any]] = None
        self._epoch = time.perf_counter()

    # -- arming (the hot-path poll only; ticks always work) ------------------

    @property
    def armed(self) -> bool:
        ov = self._override
        if ov is not None:
            return ov
        return _env_armed()

    def arm(self) -> None:
        """Advance windows from the hot-path poll regardless of
        ``SPARKDL_TPU_LEDGER``."""
        self._override = True

    def disarm(self) -> None:
        self._override = False

    def arm_from_env(self) -> None:
        self._override = None

    # -- ceilings ------------------------------------------------------------

    def ensure_ceilings(self, probe: Optional[Dict[str, Any]] = None
                        ) -> Dict[str, Any]:
        """The cached per-host ceilings, probing on first need. An
        explicit ``probe`` dict (a caller that measured the link
        itself, so the probe is never paid twice in one process) replaces
        the cache and is persisted to the probe file."""
        if probe is not None:
            probe = dict(probe)
            probe.setdefault("schema", PROBE_SCHEMA)
            with self._lock:
                self._ceilings = probe
            if _valid_probe(probe):
                try:
                    with open(self.probe_file or _default_probe_file(),
                              "w", encoding="utf-8") as f:
                        json.dump(probe, f)
                except OSError as e:
                    default_registry().counter(
                        "ledger.probe_errors").add()
                    logger.warning("ledger: cannot persist injected "
                                   "ceilings (%s)", e)
            return probe
        with self._lock:
            if self._ceilings is not None:
                return self._ceilings
        probed = probe_ceilings(path=self.probe_file)
        with self._lock:
            if self._ceilings is None:
                self._ceilings = probed
            return self._ceilings

    def _ceilings_for_tick(self) -> Dict[str, Any]:
        """The ceilings a TICK may use: whatever is already in memory,
        else a cheap READ of the probe cache file — never a measured
        probe. Ticks run inside scrape handlers, flight dumps (where
        the device may be exactly the thing that is wedged), and the
        hot-path poll; a blocking device_put probe must never ride
        those paths. With no ceilings anywhere the link lane degrades
        to transfer-wait attribution; a deliberate probe is an
        explicit :meth:`ensure_ceilings` / :func:`probe_ceilings`
        call."""
        with self._lock:
            if self._ceilings is not None:
                return self._ceilings
        path = self.probe_file or _default_probe_file()
        cached: Dict[str, Any] = {}
        if os.path.exists(path):
            try:
                with open(path, encoding="utf-8") as f:
                    data = json.load(f)
                if _valid_probe(data):
                    cached = data
            except (OSError, json.JSONDecodeError) as e:
                default_registry().counter("ledger.probe_errors").add()
                logger.warning("ledger: probe cache %s unreadable "
                               "(%s); ticking without ceilings", path,
                               e)
        if cached:
            with self._lock:
                if self._ceilings is None:
                    self._ceilings = cached
                return self._ceilings
        return {}

    # -- windowing -----------------------------------------------------------

    @staticmethod
    def _read_feeds() -> Dict[str, float]:
        reg = default_registry()
        vals = {stage: reg.counter(key).value
                for stage, key in FEEDS.items()}
        vals["link_wait"] = reg.counter(LINK_WAIT_FEED).value
        vals["link_bytes"] = reg.counter(LINK_BYTES_FEED).value
        vals["compute_flops"] = reg.counter(COMPUTE_FLOPS_FEED).value
        return vals

    def baseline(self, now: Optional[float] = None) -> None:
        """Reset the window baseline to the current feed totals, so
        that the first tick covers exactly what a caller is about to
        measure. Also drains the host
        pipeline's pooled-worker window peak (data/pipeline.py): a
        pooled experiment that finished BEFORE this baseline must not
        leak its worker count into the next window's decode ceiling
        (a serial decode-saturated pass divided by stale workers
        under-reads, and the decode-bound prior never fires)."""
        now = time.perf_counter() if now is None else now
        cur = self._read_feeds()
        from sparkdl_tpu.data.pipeline import consume_workers_peak
        consume_workers_peak()
        from sparkdl_tpu.inputsvc import client as _inputsvc
        _inputsvc.consume_workers_peak()
        with self._lock:
            self._last_t, self._last = now, cur

    def tick(self, now: Optional[float] = None, min_dt: float = 0.0
             ) -> Optional[Dict[str, Any]]:
        """Close one window: delta the feeds against the previous
        baseline, compute utilization fractions, publish the
        ``ledger.*`` gauges, append to the history ring, and return
        the window dict. Returns ``None`` without advancing anything
        for a window shorter than ``min_dt`` — including the
        zero-duration case (two ticks at one instant must not divide
        by zero or corrupt the baseline) and the racing-readers case
        (``tick_due`` passes the window length, so the loser of a
        scrape/poll race re-verifies dueness under the lock instead
        of closing a junk microsecond window over the winner's) —
        and for the very first tick (which only establishes the
        baseline). Ceilings come from memory or the cache file only
        (:meth:`_ceilings_for_tick`) — a tick never runs a measured
        probe."""
        ceilings = self._ceilings_for_tick()
        now = time.perf_counter() if now is None else now
        cur = self._read_feeds()
        with self._lock:
            if self._last_t is None:
                self._last_t, self._last = now, cur
                return None
            dt = now - self._last_t
            if dt <= 0.0 or dt < min_dt:
                return None
            last = self._last
            self._last_t, self._last = now, cur
        deltas = {k: cur.get(k, 0.0) - last.get(k, 0.0) for k in cur}
        resets = sum(1 for v in deltas.values() if v < 0)
        deltas = {k: max(0.0, v) for k, v in deltas.items()}
        # the decode lane's pooled-worker ceiling (data/pipeline.py):
        # with N host-pipeline workers live, the lane can earn N busy
        # seconds per wall second (0/1 = serial, the busy-fraction
        # ceiling unchanged). The WINDOW PEAK — max(live gauge, max
        # since the previous tick) — not an instantaneous read: a
        # pooled stream that ended mid-window already banked its N
        # busy-seconds, and dividing them by a serial ceiling would
        # fabricate a saturated decode verdict right as PipelineTarget
        # reads it as the deepen-workers prior.
        from sparkdl_tpu.data.pipeline import consume_workers_peak
        decode_workers = max(
            default_registry().gauge("pipeline.workers").value,
            consume_workers_peak())
        # the disaggregated decode fleet ADDS lanes on top of the
        # host's own (sparkdl_tpu/inputsvc): N live remote workers
        # ship N workers' busy-seconds home per wall second, beyond
        # whatever the local pool (or serial path) contributes — so
        # the ceiling is local peak + remote peak, same window-peak
        # reasoning as above (docs/DATA_SERVICE.md)
        from sparkdl_tpu.inputsvc import client as _inputsvc
        decode_workers = decode_workers + \
            _inputsvc.consume_workers_peak()
        util, link_basis, compute_basis, decode_basis = self._utils(
            deltas, dt, ceilings, decode_workers)
        verdict = attribute(util)
        window = {
            "t_s": round(now - self._epoch, 3),
            "dt_s": round(dt, 4),
            "util": verdict["util"],
            "bound_by": verdict["bound_by"],
            "headroom_pct": verdict["headroom_pct"],
            "link_basis": link_basis,
            "compute_basis": compute_basis,
            "decode_basis": decode_basis,
            "decode_workers": max(1, int(decode_workers or 0)),
            "ship_MBps": round(deltas["link_bytes"] / dt / _MB, 3),
            "counter_resets": resets,
        }
        with self._lock:
            evicting = len(self._ring) == self._ring.maxlen
            if evicting:
                self.evicted += 1
            self._ring.append(window)
            self.windows += 1
        reg = default_registry()
        for stage in STAGES:
            reg.gauge(f"ledger.util.{stage}").set(util.get(stage, 0.0))
        reg.gauge("ledger.bound_by").set(
            STAGE_CODES.get(verdict["bound_by"], -1))
        reg.gauge("ledger.headroom_pct").set(verdict["headroom_pct"])
        reg.counter("ledger.windows").add()
        if resets:
            reg.counter("ledger.counter_resets").add(resets)
        if evicting:
            # the bounded ring evicts its oldest window — counted,
            # never silent (the tracer drop-note discipline)
            reg.counter("ledger.windows_evicted").add()
        # HBM accounting rides the window cadence: per-device
        # memory_stats() → hbm.* gauges with high-watermark tracking
        # (obs/compile_log.py; degrades internally — CPU devices
        # report nothing and hbm.devices_reporting says so)
        try:
            from sparkdl_tpu.obs.compile_log import publish_hbm
            publish_hbm(reg)
        except Exception as e:
            reg.counter("ledger.config_errors").add()
            logger.debug("ledger: hbm publish failed (%s)", e)
        return window

    @staticmethod
    def _utils(deltas: Dict[str, float], dt: float,
               ceilings: Dict[str, Any],
               decode_workers: float = 0.0) -> tuple:
        """(utilization fractions, link basis, compute basis, decode
        basis) for one window. Time lanes are busy fractions of the
        window wall; the link lane is shipped bytes/s over the probed
        bandwidth, degrading to the transfer-wait fraction when no
        probe is available; the compute lane is executed FLOPs/s over
        the model-calibrated device ceiling (``device_gflops`` in the
        ceilings, injected through :meth:`ensure_ceilings`) when BOTH the ceiling and the
        flops feed exist, degrading to the dispatch+drain busy
        fraction (``compute_basis`` names which — the ``link_basis``
        mirror). The DECODE lane has the same two-tier shape
        (``decode_basis``): with N host-pipeline workers live at any
        point in the window (the window peak of the
        ``pipeline.workers`` gauge, data/pipeline.py) the ceiling is
        N busy-seconds per wall second — N workers each fully busy IS
        the lane's roofline — degrading to the plain busy fraction
        when the pipeline runs serial."""
        clamp = lambda v: min(1.0, max(0.0, v))  # noqa: E731
        util = {stage: clamp(deltas[stage] / dt) for stage in FEEDS}
        workers = max(1.0, float(decode_workers or 0.0))
        if workers > 1.0:
            util["decode"] = clamp(
                deltas["decode"] / (dt * workers))
            decode_basis = "busy/pooled-workers"
        else:
            decode_basis = "busy-time"
        bw = ceilings.get("link_h2d_MBps") if ceilings else None
        if isinstance(bw, (int, float)) and bw > 0:
            util["link"] = clamp(
                (deltas["link_bytes"] / dt) / (bw * _MB))
            basis = "bytes/probed-bandwidth"
        else:
            util["link"] = clamp(deltas["link_wait"] / dt)
            basis = "transfer-wait"
        gflops = ceilings.get("device_gflops") if ceilings else None
        flops = deltas.get("compute_flops", 0.0)
        if isinstance(gflops, (int, float)) and gflops > 0 and flops > 0:
            util["compute"] = clamp(
                (flops / dt) / (gflops * 1e9))
            compute_basis = "flops/model-ceiling"
        else:
            compute_basis = "busy-time"
        return util, basis, compute_basis, decode_basis

    def tick_due(self, now: Optional[float] = None
                 ) -> Optional[Dict[str, Any]]:
        """Tick iff a full window has elapsed since the last one (or
        no baseline exists yet). The reader-driven entry point —
        scrapes and flight dumps call this, so a hammered ``/metricsz``
        cannot shrink windows below ``window_s``. Racing callers are
        safe: ``min_dt`` makes the loser re-verify dueness inside the
        tick's critical section and back off instead of closing a
        duplicate near-zero window."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            due = (self._last_t is None
                   or (now - self._last_t) >= self.window_s)
        if due:
            # sparkdl-lint: allow[H17] -- window_s is immutable config after __init__; the hold above guards _last_t, window_s just rode inside it
            return self.tick(now=now, min_dt=self.window_s)
        return None

    # -- readout -------------------------------------------------------------

    def history(self) -> List[Dict[str, Any]]:
        """The retained windows, oldest first (bounded ring)."""
        with self._lock:
            return list(self._ring)

    def last_window(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def last_bound(self, max_age_s: Optional[float] = None
                   ) -> Optional[str]:
        """The most recent window's verdict, or ``None`` when no
        window exists (or the last one is older than ``max_age_s`` —
        a stale verdict is no prior at all)."""
        w = self.last_window()
        if w is None:
            return None
        if max_age_s is not None:
            age = (time.perf_counter() - self._epoch) - w["t_s"]
            if age > max_age_s:
                return None
        return w["bound_by"]

    def current_verdict(self) -> Dict[str, Any]:
        """The last window's verdict when one exists, else a
        cumulative attribution over the process lifetime (feed totals
        over seconds since this ledger's epoch) — what
        ``throughput_report`` prints when no windowing ran."""
        w = self.last_window()
        if w is not None:
            return {"bound_by": w["bound_by"],
                    "headroom_pct": w["headroom_pct"],
                    "util": w["util"], "basis": "window"}
        now = time.perf_counter()
        dt = max(now - self._epoch, 1e-9)
        totals = self._read_feeds()
        with self._lock:
            ceilings = self._ceilings or {}
        # cumulative totals include any pooled busy-seconds this
        # process ever banked — divide the decode lane by the
        # process-lifetime worker high-water, not the serial ceiling
        from sparkdl_tpu.data.pipeline import alltime_workers_peak
        from sparkdl_tpu.inputsvc import client as _inputsvc
        util, _basis, _cbasis, _dbasis = self._utils(
            totals, dt, ceilings,
            alltime_workers_peak()
            + _inputsvc.alltime_workers_peak())
        v = attribute(util)
        v["basis"] = "cumulative"
        return v

    def status(self) -> Dict[str, Any]:
        """The scrape-able state (``/statusz``, flight bundles)."""
        with self._lock:
            ceilings = self._ceilings
            last = self._ring[-1] if self._ring else None
            return {
                "armed": self.armed,
                "window_s": self.window_s,
                "windows": self.windows,
                "history_len": len(self._ring),
                "history_capacity": self.history_capacity,
                "evicted": self.evicted,
                "ceilings": ceilings,
                "last": last,
            }

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        # the lock, baseline, and history ring are process-local
        # (windows measured here are this process's record);
        # configuration — window length, ring capacity, ceilings,
        # armed-ness — travels
        state = self.__dict__.copy()
        del state["_lock"]
        del state["_ring"]
        del state["_last_t"]
        del state["_last"]
        del state["_epoch"]
        state["windows"] = 0
        state["evicted"] = 0
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=self.history_capacity)
        self._last_t = None
        self._last = None
        self._epoch = time.perf_counter()


_LEDGER = UtilizationLedger()


def ledger() -> UtilizationLedger:
    """THE process-wide ledger every reader (scrapes, flight bundles,
    throughput_report) consults."""
    return _LEDGER


def ledger_poll() -> None:
    """The hot-path window advancer (runner.run epilogue, the serve
    dispatcher — the ``autotune.poll`` precedent): when the ledger is
    armed and a window has elapsed, close it. Disarmed this is one
    armed-check — the shared-no-op regime, <10 µs pinned in
    tests/test_ledger.py."""
    led = _LEDGER
    if not led.armed:
        return
    led.tick_due()
