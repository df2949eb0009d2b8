"""Deterministic fault injection: the drill harness the recovery
machinery is proved against.

Every resilience mechanism in this tree — engine partition retry,
serve micro-batch re-dispatch, circuit breaking, priority shedding —
is only trustworthy if failure can be *produced on demand*,
deterministically, at the exact seam it must survive. The harness is a
set of NAMED SITES threaded through the hot paths; each armed site
draws from its own seeded RNG and raises a typed fault at the
configured rate:

========================  ==================================================
site                      where it fires
========================  ==================================================
``engine.source_load``    ``LocalEngine`` partition source load
``engine.stage_apply``    every engine stage call (pooled + stream paths)
``ship.device_put``       per-chunk input placement in ``dispatch_chunks``
``ship.drain``            per-batch result drain (``drain_bounded``)
``collective.launch``     entering the collective launch lock
``serve.dispatch``        the serve dispatcher's micro-batch runner call
``model.fetch``           ``ModelFetcher`` cache/weight reads
``pipeline.worker_decode``  per-task decode inside a pipeline WORKER process
``pipeline.worker_death``   kills a live pipeline worker process outright
``inputsvc.rpc``          the decode fleet's per-fragment RPC (client side)
``snapshot.read``         a snapshot chunk's warm read (corrupt/missing drill)
``fleet.swap``            the registry's hot-swap flip, after staging,
                          before commit (mid-swap rollback drill)
``fleet.route``           the fleet router's per-replica pick (failover
                          drill)
========================  ==================================================

The two ``pipeline.worker_*`` sites fire inside pool worker
*processes*: workers inherit ``SPARKDL_TPU_FAULTS`` through the
environment (fork and spawn both re-run :func:`arm_from_env` at
import), and the cross-process telemetry plane
(:mod:`sparkdl_tpu.obs.remote`) additionally ships a parent's
*programmatic* spec to workers via :func:`arm_spec`, so
``inject(...)`` drills reach the worker fleet too.
``pipeline.worker_death`` is the ROADMAP-named worker-death drill: the
task handler converts the injected fault into ``os._exit(1)`` — a real
process corpse, a real ``BrokenProcessPool``, not a simulated error.

Arming:

* ``SPARKDL_TPU_FAULTS=<site>:<kind>:<rate>[:seed]`` (comma-separate
  several sites), parsed once at import — kinds are ``transient``
  (raises :class:`InjectedFault`, the retryable drill) and
  ``permanent`` (raises :class:`InjectedPermanentFault`, the
  fail-fast drill); ``rate`` is the per-call injection probability in
  (0, 1]; ``seed`` defaults to 0. A malformed env spec degrades to
  disarmed with one warning (the watchdog-threshold precedent) —
  a typo must not take down a serving process.
* programmatic :func:`inject`/:func:`disarm` for tests and drills
  (explicit API, so bad arguments raise :class:`FaultSpecError`
  loudly instead of degrading).

Accounting: every injection counts in the ``faults.injected`` registry
counter plus its per-site ``faults.<site>.injected`` (a bounded,
documented key family — rule H6/H9); :func:`state` renders the armed
config + per-site counts for flight bundles and ``/statusz``.

Disarmed, :func:`maybe_fail` is one module-global read and a ``None``
check — the tracer's shared no-op regime, pinned <10µs/call alongside
the span bound in ``tests/test_resilience.py``.
"""

from __future__ import annotations

import logging
import os
import random
import threading
from typing import Dict, Optional

from sparkdl_tpu.obs.registry import default_registry
from sparkdl_tpu.resilience.errors import PermanentError, TransientError

logger = logging.getLogger(__name__)

#: every site threaded through the tree (module table above) — the
#: harness refuses unknown names so a drill config typo cannot arm a
#: site that nothing ever checks
SITES = (
    "engine.source_load",
    "engine.stage_apply",
    "ship.device_put",
    "ship.drain",
    "collective.launch",
    "serve.dispatch",
    "model.fetch",
    "pipeline.worker_decode",
    "pipeline.worker_death",
    "inputsvc.rpc",
    "snapshot.read",
    "fleet.swap",
    "fleet.route",
)

_KINDS = ("transient", "permanent")


class FaultSpecError(ValueError):
    """A programmatic :func:`inject` call named an unknown site/kind or
    an out-of-range rate."""


class InjectedFault(TransientError):
    """A transient injected fault — classified retryable by
    :func:`~sparkdl_tpu.resilience.errors.is_transient`, so the retry
    and circuit machinery exercises its recovery path."""


class InjectedPermanentFault(PermanentError):
    """A permanent injected fault — classified NON-retryable, so
    fail-fast paths (typed propagation, circuit opening) exercise
    without the retry layer absorbing the drill."""


class _SiteFault:
    """One armed site: its kind, rate, and a private seeded RNG (one
    deterministic draw sequence per site per arm)."""

    # sparkdl-lint H3 contract: hot-path threads (engine pool workers,
    # serve dispatchers) check concurrently — the RNG draw and the
    # counters hold self._lock
    _lock_guards = ("checks", "injected")

    def __init__(self, site: str, kind: str, rate: float, seed: int):
        self.site = site
        self.kind = kind
        self.rate = float(rate)
        self.seed = int(seed)
        self.checks = 0
        self.injected = 0
        self._rng = random.Random(self.seed)
        self._lock = threading.Lock()

    def check(self) -> None:
        with self._lock:
            self.checks += 1
            fire = self._rng.random() < self.rate
            if fire:
                self.injected += 1
        if not fire:
            return
        reg = default_registry()
        reg.counter("faults.injected").add()
        # bounded key family: sites are the fixed SITES tuple, never a
        # per-request value (rules H6/H9; documented in
        # docs/OBSERVABILITY.md)
        reg.counter(f"faults.{self.site}.injected").add()
        if self.kind == "permanent":
            raise InjectedPermanentFault(
                f"injected permanent fault at {self.site} "
                f"(rate={self.rate}, seed={self.seed})")
        raise InjectedFault(
            f"injected transient fault at {self.site} "
            f"(rate={self.rate}, seed={self.seed})")

    def state(self) -> dict:
        with self._lock:
            return {"kind": self.kind, "rate": self.rate,
                    "seed": self.seed, "checks": self.checks,
                    "injected": self.injected}

    # locks don't pickle (H3); drill state is process-local but the
    # config travels so a shipped closure can re-describe its drill
    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        del state["_rng"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._rng = random.Random(self.seed)
        self._lock = threading.Lock()


#: the armed plan: ``None`` = disarmed (THE fast-path check). A plain
#: dict replaced wholesale on every (re)arm, so readers never see a
#: half-built plan and the hot path takes no lock when disarmed.
_PLAN: Optional[Dict[str, _SiteFault]] = None
_SPEC: str = ""     # the spec string the plan was built from (state())


def maybe_fail(site: str) -> None:
    """The per-site hook the hot paths call. Disarmed (no plan, or a
    plan without this site): one global read + a dict probe at most —
    the shared no-op regime. Armed: one seeded draw; at the configured
    rate, counts the injection and raises the typed fault."""
    plan = _PLAN
    if plan is None:
        return
    sf = plan.get(site)
    if sf is not None:
        sf.check()


def inject(site: str, kind: str = "transient", rate: float = 1.0,
           seed: int = 0) -> None:
    """Programmatically arm one site (drills, tests); repeated calls
    add/replace sites without touching others. Loud on bad arguments —
    an explicit drill config is code, not environment."""
    if site not in SITES:
        raise FaultSpecError(
            f"unknown fault site {site!r}; sites: {', '.join(SITES)}")
    if kind not in _KINDS:
        raise FaultSpecError(
            f"unknown fault kind {kind!r}; kinds: {', '.join(_KINDS)}")
    if not 0.0 < float(rate) <= 1.0:
        raise FaultSpecError(
            f"rate must be in (0, 1], got {rate}")
    global _PLAN, _SPEC
    plan = dict(_PLAN or {})
    plan[site] = _SiteFault(site, kind, float(rate), int(seed))
    _SPEC = ",".join(f"{s}:{f.kind}:{f.rate}:{f.seed}"
                     for s, f in sorted(plan.items()))
    _PLAN = plan


def disarm(site: Optional[str] = None) -> None:
    """Disarm one site, or (no argument) the whole harness."""
    global _PLAN, _SPEC
    if site is None or _PLAN is None:
        _PLAN = None
        _SPEC = ""
        return
    plan = {s: f for s, f in _PLAN.items() if s != site}
    _PLAN = plan or None
    _SPEC = ",".join(f"{s}:{f.kind}:{f.rate}:{f.seed}"
                     for s, f in sorted(plan.items()))


def armed() -> bool:
    return _PLAN is not None


def spec() -> str:
    """The armed spec string (``""`` disarmed) — what the telemetry
    plane ships to worker processes so a parent-side ``inject()``
    drill arms the fleet (:mod:`sparkdl_tpu.obs.remote`)."""
    return _SPEC


def state() -> dict:
    """The harness state for flight bundles / ``/statusz``:
    armed-ness, the effective spec, and per-site config + counts."""
    plan = _PLAN
    return {
        "armed": plan is not None,
        "spec": _SPEC,
        "sites": {s: f.state() for s, f in sorted((plan or {}).items())},
    }


def _parse_env(spec: str) -> Optional[Dict[str, _SiteFault]]:
    """``site:kind:rate[:seed]`` comma list → plan; None on any
    malformed entry (the caller degrades with one warning — env typos
    must not break imports)."""
    plan: Dict[str, _SiteFault] = {}
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) not in (3, 4):
            return None
        site, kind, rate = parts[0].strip(), parts[1].strip(), parts[2]
        seed = parts[3] if len(parts) == 4 else "0"
        if site not in SITES or kind not in _KINDS:
            return None
        try:
            rate_f = float(rate)
            seed_i = int(seed)
        except ValueError:
            return None
        if not 0.0 < rate_f <= 1.0:
            return None
        plan[site] = _SiteFault(site, kind, rate_f, seed_i)
    return plan or None


def arm_spec(raw: str) -> bool:
    """Arm from an explicit spec string — the same grammar and
    degrade contract as the env path. This is how a worker-side
    telemetry agent applies the parent's shipped spec
    (:mod:`sparkdl_tpu.obs.remote`): a malformed spec degrades to the
    current plan with one warning, never an unimportable worker."""
    global _PLAN, _SPEC
    raw = (raw or "").strip()
    if not raw:
        return _PLAN is not None
    plan = _parse_env(raw)
    if plan is None:
        logger.warning(
            "%r is not a valid fault spec "
            "(<site>:<kind>:<rate>[:seed], comma-separated; sites: %s; "
            "kinds: %s); fault injection stays disarmed",
            raw, ", ".join(SITES), ", ".join(_KINDS))
        return _PLAN is not None
    _PLAN = plan
    _SPEC = raw
    return True


def arm_from_env() -> bool:
    """Apply ``SPARKDL_TPU_FAULTS`` (idempotent; also runs at import).
    Returns whether the harness ended up armed. A malformed spec
    degrades to disarmed with one warning — the config-typo
    discipline every env knob in this tree follows."""
    spec_str = os.environ.get("SPARKDL_TPU_FAULTS", "").strip()
    if not spec_str:
        return _PLAN is not None
    return arm_spec(spec_str)


arm_from_env()
