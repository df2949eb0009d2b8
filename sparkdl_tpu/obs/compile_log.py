"""Compile forensics: retrace attribution, cost/memory accounting,
and runtime-enforced zero-retrace guarantees.

The package's whole serving story hinges on *never paying compile on
the hot path*: PR 4's one-compiled-shape guarantee, PR 6's pre-warmed
shape ladder, and lint rule H2 all police retraces — but statically or
by test pin only. At runtime a retrace was invisible: a production
process that started recompiling per request would show up as a
latency cliff with zero attribution. This module is the dynamic
counterpart of H2 — THE process-wide CompileLog every package jit
compile routes through:

* ``ModelFunction.jitted`` / ``sharded_jitted`` cache misses and
  ``device_params`` / ``replicated_params`` weight placements
  (graph/function.py), ``KerasImageFileEstimator._compile_step`` (both
  branches), StableHLO ``ModelFunction.deserialize`` — and therefore
  everything built on them: ``warmup_runner``, ``RechunkTarget
  .prewarm`` rungs, every serve dispatch.
* each event records the callable name, the abstract argument
  signature (per-arg shapes/dtypes/shardings + donate config), the
  FIRST-CALL wall (``wall_s``: trace + lower + compile + the first
  batch's execution, an upper bound on compile), beside it what JAX
  itself reports of that call's phases (``trace_s``, ``lower_s``,
  ``backend_s``, ``cache``: "The phases" below) — and, where the
  backend supports it — ``compiled.cost_analysis()`` FLOPs/bytes and
  ``memory_analysis()`` buffer sizes (both degrade to ``None`` on
  backends that return nothing, e.g. some CPU builds).
* **retrace attribution**: a recompile of a known function records a
  signature DIFF naming the offending argument(s) — ``inputs.image:
  uint8[64,32,32,3] -> uint8[48,32,32,3]`` — so a compile storm names
  its cause instead of being a mystery latency cliff.
* **the steady contract** (the enforcement): ``warmup_runner`` and
  ``RechunkTarget.prewarm`` mark a model's instrumented programs
  *steady* once their warm shapes are compiled. Any REAL compile
  through a steady program afterwards counts
  ``compile.unexpected_retraces``, logs at ERROR with the diff, fires
  a flight-recorder dump (armed recorders only — the
  ``record_failure`` discipline), and surfaces on ``/healthz`` detail
  — PR 4/6's warm-start guarantees become runtime invariants, not
  just test pins.

Compile detection is TRUTHFUL, not inferred: the wrapper tracks the
signatures it has seen, but a signature miss only records an event
when the underlying jit executable cache actually GREW
(``fn._cache_size()``) — so arming the log mid-process against a
warm jit cache records nothing, and warm-while-disarmed shapes never
read as retraces. Backends without ``_cache_size`` degrade to
signature-based detection (documented, never silent in the event:
``verified`` says which).

The phases (always on, armed or not): THE log listens to JAX's own
``jax.monitoring`` events, which fire only where something is traced,
lowered or compiled, so a steady call never reaches a listener.
``/jax/core/compile/jaxpr_trace_duration``,
``.../jaxpr_to_mlir_module_duration`` and
``.../backend_compile_duration`` (on a persistent-cache hit the last
is the read) each announce their start (``record_scalar``) and their
end (the duration), so a per-thread stack tells an OUTERMOST event
from one that closes inside another (an inner ``jax.jit``, a
primitive's own jit): only outermost events are addends of a total,
children are kept under their parent's name. The cache's events
(``compile_requests_use_cache``, ``cache_hits``, ``cache_misses``,
which fires where an entry is WRITTEN, and
``cache_retrieval_time_sec``) fire inside a backend-compile event and
belong to the one that closes next on their thread. Whose compile it
was is told by the name JAX reports (``fun_name``: the jitted
function's ``__name__``, wrapped as ``jit(<name>)`` on lower and
backend events), which ``instrument`` learns when it wraps the
function. Outermost events of instrumented functions feed
``compile.trace_seconds`` / ``lower_seconds`` / ``backend_seconds`` /
``programs`` / ``cache_requests`` / ``cache_hits`` / ``cache_writes``
/ ``cache_read_seconds``; all others
``compile.uninstrumented_seconds``; those of ``_analyze``'s own
``lower().compile()`` ``compile.analysis_seconds``. A function whose
``__name__`` is nobody's own (``<lambda>``, none at all) cannot be
told from the crowd and counts as uninstrumented; a name as common as
the estimator's ``step`` is booked to the program wherever it is
compiled. ``CompileLog.phases()`` reads the bounded table by name.
With the log AND the tracer armed each event, nested ones too, is
also a ``compile.trace`` / ``compile.lower`` / ``compile.backend``
span, stamped post hoc on ``perf_counter``'s clock (JAX's own stamps
are ``time.time()`` and are not used). Only THE process-wide log
listens; a standalone instance (tests) hears nothing.

Arming: ``SPARKDL_TPU_COMPILE_LOG=1`` or ``compile_log().arm()`` (the
override wins — the tracer convention). Disarmed, every instrumented
call is ONE armed-check and a passthrough — no signature walk, no
lock, no ring growth (<10 µs pinned in tests/test_compile_log.py).
Armed, a seen-signature call pays one memoized signature walk; the
full cost/memory analysis runs only on actual compiles (and the
second ``lower().compile()`` it needs rides jit's own cache, or the
persistent XLA compilation cache where configured:
``utils/compile_cache.configure_compile_cache``; what it costs is
``compile.analysis_seconds`` of ``compile.analysis_wall_seconds``).

HBM accounting rides here too: :func:`publish_hbm` promotes per-device
``memory_stats()`` from a flight-dump snapshot to periodic ``hbm.*``
registry gauges with high-watermark tracking — called per ledger
window (obs/ledger.py), per ``/metricsz`` scrape, and per flight
bundle; CPU devices report nothing and ``hbm.devices_reporting`` says
so rather than omitting the lane.

Ring-buffer discipline (the tracer precedent): events retain in a
bounded ring (``SPARKDL_TPU_COMPILE_LOG_CAPACITY``, default 512,
typo-degrade); evictions count ``compile.events_dropped`` — never a
silent truncation. Pickle discipline (StageMetrics precedent): the
lock, the event ring, and the per-function tables drop on the wire —
compiles observed in one process are that process's record; the
capacity and armed-ness override travel.
"""

from __future__ import annotations

import collections
import logging
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from sparkdl_tpu.obs.registry import default_registry
from sparkdl_tpu.obs.trace import tracer

logger = logging.getLogger(__name__)

_TRUE = ("1", "true", "yes", "on")

#: event-ring capacity when SPARKDL_TPU_COMPILE_LOG_CAPACITY is unset
DEFAULT_CAPACITY = 512

#: known signatures retained per function for diffing (bounded — a
#: pathological per-call-shape caller must not grow the table; the
#: diff always compares against the most recent)
SIGNATURES_PER_FUNCTION = 16

#: per-wrapper seen-signature / flops table bound: a per-request-shape
#: compile storm (exactly what this module exists to diagnose) must
#: not grow wrapper memory without bound. Eviction is SAFE because the
#: jit-cache-size truth gate re-verifies an evicted-and-recurring
#: signature (cache warm -> no event) before it could re-record.
SEEN_PER_WRAPPER = 4096

#: memo slot for an identity-UNSTABLE positional arg (a fresh inputs
#: dict per dispatch): walk it every call, retain nothing — only
#: identity-stable args (the params pytree) earn a cached signature,
#: so the wrapper never pins a transient batch for the model's
#: lifetime
_UNSTABLE = object()

CompileEvent = collections.namedtuple(
    "CompileEvent",
    ["seq", "name", "kind", "signature", "config", "wall_s",
     "retrace", "unexpected", "diff", "cost", "memory", "verified",
     "t_s", "module", "scopes", "trace_s", "lower_s", "backend_s",
     "cache"],
    defaults=(None,) * 6)

#: the jax.monitoring events that announce their start and their end,
#: by the phase they time
_PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
#: the persistent cache's events, by the tally they move, under the
#: names ``phases()`` gives them (JAX's ``cache_misses`` fires where an
#: entry is WRITTEN: a compile under the cache's size or time threshold
#: fires neither it nor a hit); the last is a duration
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_writes",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
#: every always-on counter the listeners feed; all are made when THE
#: log starts to listen, so that a reader tells "none yet" (0) from
#: "a program that keeps no such counter" (absent)
PHASE_COUNTERS = (
    "compile.trace_seconds", "compile.lower_seconds",
    "compile.backend_seconds", "compile.programs",
    "compile.cache_requests", "compile.cache_hits",
    "compile.cache_writes", "compile.cache_read_seconds",
    "compile.uninstrumented_seconds", "compile.analysis_seconds",
    "compile.analysis_wall_seconds", "compile.phases_dropped")
_WRAPPED_NAME = re.compile(r"^\w+\((.*)\)$")


def reported_name(fn: Any) -> Optional[str]:
    """The name JAX reports for a jitted callable's trace, lower and
    compile events (its ``__name__``; lower and backend events wrap it
    as ``jit(<name>)``), or None where that name is nobody's own."""
    name = getattr(fn, "__name__", None)
    if not isinstance(name, str) or not name or name == "<lambda>":
        return None
    return name


def _bare_name(fun_name: str) -> str:
    m = _WRAPPED_NAME.match(fun_name)
    return m.group(1) if m else fun_name

_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bop_name=\"([^\"]*)\"")
_JIT_COMPONENT = re.compile(r"^\w*jit\(.*\)$")


def instruction_scopes(hlo_text: str) -> Tuple[Optional[str],
                                                Dict[str, str]]:
    """The compiled program's name (``jit_InceptionV3_featurize``, as
    on the profiler's ``XLA Modules`` line) and its
    instruction-to-scope map: instruction name (``fusion.26``, as the
    profiler's ``XLA Ops`` events begin) to the module path in its
    ``op_name``, with the ``jit(...)`` components and the primitive's
    own name cut off (``jit(InceptionV3_featurize)/InceptionV3/``
    ``InceptionBlockA_0/ConvBN_2/Conv_0/conv_general_dilated`` gives
    ``InceptionV3/InceptionBlockA_0/ConvBN_2/Conv_0``). An
    instruction without an ``op_name``, or whose ``op_name`` names no
    module (a parameter, an operation at the program's top level), has
    no entry."""
    head = _HLO_MODULE.match(hlo_text)
    scopes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            continue
        path = [c for c in m.group(2).split("/")
                if not _JIT_COMPONENT.match(c)][:-1]
        if path:
            scopes[m.group(1)] = "/".join(path)
    return (head.group(1) if head else None), scopes


def _env_armed() -> bool:
    return os.environ.get("SPARKDL_TPU_COMPILE_LOG", "").lower() in _TRUE


def _env_capacity() -> int:
    # the module-level singleton parses this at import time — a config
    # typo must degrade to the default, not make the package
    # unimportable (the SPARKDL_TPU_TRACE_BUFFER precedent)
    raw = os.environ.get("SPARKDL_TPU_COMPILE_LOG_CAPACITY", "")
    if not raw:
        return DEFAULT_CAPACITY
    try:
        cap = int(raw)
        if cap <= 0:
            raise ValueError(cap)
        return cap
    except ValueError:
        logger.warning(
            "SPARKDL_TPU_COMPILE_LOG_CAPACITY=%r is not a positive "
            "int; using the default %d", raw, DEFAULT_CAPACITY)
        default_registry().counter("compile.config_errors").add()
        return DEFAULT_CAPACITY


# -- abstract signatures ------------------------------------------------------

def describe_leaf(v: Any) -> str:
    """One argument leaf as a canonical string: ``dtype[shape]`` plus
    a sharding tag for non-trivially-sharded device arrays (the
    signature components a jit cache keys on)."""
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is None or dtype is None:
        return f"py:{type(v).__name__}"
    desc = f"{dtype}[{','.join(str(int(d)) for d in shape)}]"
    sharding = getattr(v, "sharding", None)
    if sharding is not None:
        s = type(sharding).__name__
        if s not in ("SingleDeviceSharding",):
            desc += f"@{s}:{str(sharding)[:64]}"
    return desc


def abstract_signature(args: tuple, kwargs: Optional[dict] = None,
                       arg_names: Optional[Tuple[str, ...]] = None
                       ) -> Dict[str, str]:
    """Flatten a call's arguments into ``{path: leaf-desc}`` — dict
    keys and list indexes join the path, so the retrace diff can name
    ``inputs.image`` rather than ``arg1``."""
    sig: Dict[str, str] = {}

    def walk(prefix: str, v: Any) -> None:
        if isinstance(v, dict):
            for k in sorted(v, key=str):
                walk(f"{prefix}.{k}" if prefix else str(k), v[k])
        elif isinstance(v, (list, tuple)):
            for i, item in enumerate(v):
                walk(f"{prefix}[{i}]", item)
        else:
            sig[prefix] = describe_leaf(v)

    for i, a in enumerate(args):
        name = (arg_names[i] if arg_names and i < len(arg_names)
                else f"arg{i}")
        walk(name, a)
    for k, v in (kwargs or {}).items():
        walk(str(k), v)
    return sig


def signature_diff(prev: Dict[str, str], cur: Dict[str, str]) -> str:
    """The retrace attribution: every argument path whose abstract
    value changed, ``name: old -> new`` (absent sides named too)."""
    parts = []
    for k in sorted(set(prev) | set(cur)):
        a, b = prev.get(k), cur.get(k)
        if a != b:
            parts.append(f"{k}: {a or '(absent)'} -> {b or '(absent)'}")
    return "; ".join(parts)


# -- the instrumented-callable wrapper ----------------------------------------

class _LoggedJit:
    """The routing wrapper around one jitted callable: disarmed it is
    one armed-check + passthrough; armed it tracks seen signatures and
    hands signature misses to the CompileLog (which verifies an actual
    compile happened via the jit cache size before recording)."""

    # sparkdl-lint H3 contract: concurrent runner threads dispatch
    # through one wrapper — the seen-signature table holds self._lock
    _lock_guards = ("_seen",)

    def __init__(self, fn: Callable, name: str, kind: str,
                 config: Optional[dict],
                 arg_names: Optional[Tuple[str, ...]], log: "CompileLog"):
        self._fn = fn
        self._name = name
        self._kind = kind
        self._config = dict(config or {})
        self._arg_names = tuple(arg_names) if arg_names else None
        self._log = log
        # the name JAX will report for this function's trace, lower
        # and compile events: learnt here, once, so that telling whose
        # compile it was adds nothing to a call
        self._jax_name = reported_name(fn)
        log._know(self._jax_name)
        # insertion-ordered, bounded at SEEN_PER_WRAPPER (oldest
        # evicts; the cache-size truth gate keeps eviction safe)
        self._seen: Dict[tuple, bool] = {}
        # cost_analysis FLOPs per seen signature — the per-SHAPE
        # record behind last_flops (a multi-shape compile history,
        # e.g. a prewarmed ladder, must not credit every dispatch
        # with the largest rung's FLOPs)
        self._flops_by_key: Dict[tuple, float] = {}
        # per-positional-arg signature memo keyed by object identity
        # (strong ref + `is` check, the _params_cache precedent): the
        # params pytree is the same object call-to-call, so its
        # potentially-hundreds-of-leaves walk is paid once
        self._memo: Dict[int, Tuple[Any, Dict[str, str]]] = {}
        self._lock = threading.Lock()
        self.steady = False
        #: cost_analysis FLOPs of the most recently DISPATCHED
        #: signature (armed calls refresh it per call from
        #: _flops_by_key) — the ledger's model-specific compute feed
        #: reads this (runtime/runner.py record_run_feeds), so it must
        #: track the shape actually running, not the shape most
        #: recently compiled
        self.last_flops: Optional[float] = None

    @property
    def __wrapped__(self) -> Callable:
        return self._fn

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def mark_steady(self) -> None:
        """After this, any REAL compile through this program counts
        ``compile.unexpected_retraces`` (the warmup/prewarm contract)."""
        self.steady = True

    def signature(self, args: tuple, kwargs: dict) -> Dict[str, str]:
        sig: Dict[str, str] = {}
        for i, a in enumerate(args):
            m = self._memo.get(i)
            if m is not None and m is not _UNSTABLE and m[0] is a:
                sig.update(m[1])
                continue
            name = (self._arg_names[i]
                    if self._arg_names and i < len(self._arg_names)
                    else f"arg{i}")
            part = abstract_signature((a,), arg_names=(name,))
            if m is None:
                # first sighting: assume identity-stable (the params
                # pytree) and cache the walk
                self._memo[i] = (a, part)
            elif m is not _UNSTABLE:
                # second distinct object at this position: this arg is
                # a per-call transient (the inputs dict) — stop
                # retaining it, a wrapper must never pin a dead batch
                # for the ModelFunction's lifetime
                self._memo[i] = _UNSTABLE
            sig.update(part)
        if kwargs:
            sig.update(abstract_signature((), kwargs))
        return sig

    def __call__(self, *args, **kwargs):
        log = self._log
        if not log.armed:
            return self._fn(*args, **kwargs)
        sig = self.signature(args, kwargs)
        key = tuple(sorted(sig.items()))
        if key in self._seen:
            # refresh the per-dispatch FLOPs record: the ledger feed
            # must credit the shape RUNNING now, not the shape most
            # recently compiled (a prewarmed ladder's last rung) —
            # and a shape whose analysis degraded feeds None, never a
            # stale neighbor's number
            self.last_flops = self._flops_by_key.get(key)
            return self._fn(*args, **kwargs)
        return self._first_call(args, kwargs, sig, key)

    def _cache_size(self) -> Optional[int]:
        probe = getattr(self._fn, "_cache_size", None)
        if probe is None:
            return None
        try:
            return int(probe())
        except Exception:
            return None

    def _first_call(self, args, kwargs, sig, key):
        # claim the signature BEFORE calling: a racing second thread
        # sees it seen and just calls (its call blocks inside jax's own
        # compile lock) — one compile, one event. The claim is rolled
        # back on failure so a crashed compile stays observable.
        with self._lock:
            if key in self._seen:
                claimed = False
            else:
                self._seen[key] = True
                claimed = True
                while len(self._seen) > SEEN_PER_WRAPPER:
                    # bounded wrapper memory under a compile storm;
                    # an evicted signature that recurs re-verifies
                    # through the cache-size gate (no false event)
                    evicted = next(iter(self._seen))
                    del self._seen[evicted]
                    self._flops_by_key.pop(evicted, None)
        if not claimed:
            return self._fn(*args, **kwargs)
        # the most recently seen OTHER signature: the diff baseline
        # even when that signature's compile predates arming (it was
        # seen, cache-warm, and recorded nothing — but it still names
        # what the offending argument moved FROM)
        with self._lock:
            prior = [k for k in self._seen if k != key]
        prev_sig = dict(prior[-1]) if prior else None
        before = self._cache_size()
        log = self._log
        log._open_capture(self._jax_name)
        t0 = time.perf_counter()
        try:
            out = self._fn(*args, **kwargs)
        except BaseException:
            log._close_capture()
            with self._lock:
                self._seen.pop(key, None)
            raise
        end = time.perf_counter()
        phases = log._close_capture()
        after = self._cache_size()
        # the truth gate: only a GROWN executable cache is a compile —
        # a warm-while-disarmed shape re-seen after arming is not.
        # Backends without _cache_size degrade to signature-based
        # detection (verified=False on the event).
        verified = before is not None and after is not None
        compiled = after > before if verified else True
        if compiled:
            log._record_compile(
                self, args, kwargs, sig, key, wall_s=end - t0, t0=t0,
                t_end=end, verified=verified, prev_signature=prev_sig,
                phases=phases)
        return out

    # pickle discipline (StageMetrics precedent): the lock drops; the
    # seen table and memo are process-local observations and drop with
    # it (the receiving process re-observes); the wrapped fn travels
    # iff it can (ModelFunction drops its whole jit cache anyway).
    # The log reference re-binds to the RECEIVING process's singleton
    # (the _CollectiveLaunch H3 precedent) — a shipped wrapper must
    # record into the process-wide table, not a dead clone, except
    # when it was bound to a standalone (test) instance, whose clone
    # travels with it.
    def __getstate__(self):
        state = self.__dict__.copy()
        del state["_lock"]
        state["_seen"] = {}
        state["_memo"] = {}
        state["_flops_by_key"] = {}
        if state["_log"] is _COMPILE_LOG:
            state["_log"] = None    # sentinel: re-bind on arrival
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        if self._log is None:
            self._log = _COMPILE_LOG
        self._lock = threading.Lock()
        self._log._know(self._jax_name)

    def __repr__(self) -> str:
        return (f"_LoggedJit({self._name}, kind={self._kind}, "
                f"seen={len(self._seen)}, steady={self.steady})")


class _AotProgram:
    """The persisted-warm-start seam's wrapper (fleet/warmstart.py): a
    pre-compiled executable DESERIALIZED into this process, installed
    where ``jitted()`` would cache a :class:`_LoggedJit`. Dispatches
    pass straight through; there is no signature table and no compile
    detection because this program CANNOT compile — it was built in
    another process, and an unseen shape fails loudly inside the
    executable instead of silently retracing. ``mark_steady`` /
    ``last_flops`` keep the warmup and ledger bookkeeping uniform with
    instrumented jits."""

    _lock_guards = ()

    def __init__(self, fn: Callable, name: str, kind: str,
                 log: "CompileLog"):
        self._fn = fn
        self._name = name
        self._kind = kind
        self._log = log
        # a loaded executable compiles nothing; should its name ever
        # be reported (a fallback that re-traces), it is the program's
        self._jax_name = reported_name(fn)
        log._know(self._jax_name)
        self.steady = False
        #: no cost_analysis travels with a deserialized executable —
        #: the ledger's compute feed degrades to None, never a guess
        self.last_flops: Optional[float] = None

    @property
    def __wrapped__(self) -> Callable:
        return self._fn

    def mark_steady(self) -> None:
        self.steady = True

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)

    def __repr__(self) -> str:
        return (f"_AotProgram({self._name}, kind={self._kind}, "
                f"steady={self.steady})")


# -- the log ------------------------------------------------------------------

class CompileLog:
    """Process-wide compile-event recorder (module docstring). One
    instance (:func:`compile_log`); standalone instances exist for
    tests."""

    # sparkdl-lint H3 contract: events arrive from every compiling
    # thread — ring/table/counter writes hold self._lock
    _lock_guards = ("events_total", "dropped", "unexpected_retraces",
                    "retraces", "phases_dropped")

    def __init__(self, capacity: Optional[int] = None):
        cap = capacity if capacity is not None else _env_capacity()
        if cap <= 0:
            raise ValueError(f"capacity must be positive, got {cap}")
        self.capacity = cap
        # None → follow the env; True/False → programmatic override
        self._override: Optional[bool] = None
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=cap)
        self._functions: Dict[str, Dict[str, Any]] = {}
        self._steady_models: set = set()
        self.events_total = 0
        self.dropped = 0
        self.retraces = 0
        self.unexpected_retraces = 0
        self._epoch = time.perf_counter()
        # the phases JAX reports (module docstring, "The phases"): the
        # reported names of the instrumented functions, the bounded
        # table by name (oldest evicted, counted), and per thread the
        # open events, the cache tallies waiting for their backend
        # event, _analyze's flag and the armed first call's capture
        self._names: set = set()
        self._phases: Dict[str, Dict[str, Any]] = {}
        self.phases_dropped = 0
        self._heard = threading.local()
        #: cost/memory analysis on compile events (lower().compile()
        #: once per new program — rides the persistent XLA compilation
        #: cache where configured); flip off for processes where even
        #: the cold-path double compile is unaffordable
        self.analysis_enabled = True

    # -- arming --------------------------------------------------------------

    @property
    def armed(self) -> bool:
        ov = self._override
        if ov is not None:
            return ov
        return _env_armed()

    def arm(self) -> None:
        """Record compile events regardless of
        ``SPARKDL_TPU_COMPILE_LOG``."""
        self._override = True

    def disarm(self) -> None:
        self._override = False

    def arm_from_env(self) -> None:
        self._override = None

    # -- the phases JAX reports ----------------------------------------------

    def _know(self, name: Optional[str]) -> None:
        """One more instrumented function, by the name JAX reports for
        it (None: nobody's own, so its events stay uninstrumented).
        THE log starts to listen here at the latest: whoever wraps a
        jitted function has imported jax."""
        if name:
            with self._lock:
                self._names.add(name)
        if self is _COMPILE_LOG:
            listen()

    def _thread(self):
        st = self._heard
        if not hasattr(st, "stack"):
            st.stack = []       # open events, outermost first: (phase, name)
            st.cache = None     # tallies for the backend event that closes next
            st.analysis = False
            st.capture = None
        return st

    def _open_capture(self, name: Optional[str]) -> None:
        """The armed first call of a wrapper: what is heard on this
        thread until ``_close_capture``, outermost and of this name
        (any name, where the wrapper's is nobody's own), is that
        call's ``trace_s`` / ``lower_s`` / ``backend_s`` / ``cache``."""
        self._thread().capture = {"fn": name}

    def _close_capture(self) -> Optional[dict]:
        st = self._thread()
        capture, st.capture = st.capture, None
        return capture

    def _on_start(self, event: str, value: Any = None, **kw) -> None:
        phase = _PHASE_EVENTS.get(event)
        if phase is not None:
            self._thread().stack.append(
                (phase, _bare_name(str(kw.get("fun_name", "?")))))

    def _on_event(self, event: str, amount: float = 1, **kw) -> None:
        tally = _CACHE_EVENTS.get(event)
        if tally is not None:
            st = self._thread()
            if st.cache is None:
                st.cache = dict.fromkeys(_CACHE_EVENTS.values(), 0)
            st.cache[tally] += amount

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        phase = _PHASE_EVENTS.get(event)
        if phase is None:
            self._on_event(event, float(duration))   # the cache's read
            return
        trc = tracer()
        end = (time.perf_counter() if trc.armed and self.armed
               else None)
        st = self._thread()
        name = _bare_name(str(kw.get("fun_name", "?")))
        stack = st.stack
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == (phase, name):
                # this event's frame goes, and with it any above it
                # whose own end never came
                del stack[i:]
                break
        parent = stack[0][1] if stack else None
        cache = None
        if phase == "backend":
            cache, st.cache = st.cache, None
        self._book(st, phase, name, parent, float(duration), cache)
        if end is not None:
            attrs = {"fn": name}
            if parent is not None:
                attrs["under"] = parent
            if phase == "backend":
                attrs["cache"] = _cache_verdict(cache)
            if st.analysis:
                attrs["analysis"] = True
            trc._record(f"compile.{phase}", "compile", end - duration,
                        end, attrs)

    def _book(self, st, phase: str, name: str, parent: Optional[str],
              seconds: float, cache: Optional[dict]) -> None:
        """One closed event into the table and, where it is outermost,
        into the counters of whose it was: the program's (an
        instrumented name), ``_analyze``'s own, or everybody else's."""
        key = parent if parent is not None else name
        dropped = 0
        with self._lock:
            mine = name in self._names
            entry = self._phases.get(key)
            if entry is None:
                while len(self._phases) >= self.capacity:
                    del self._phases[next(iter(self._phases))]
                    dropped += 1
                self.phases_dropped += dropped
                entry = self._phases[key] = {
                    "instrumented": key in self._names,
                    "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                    "programs": 0, "cache_requests": 0, "cache_hits": 0,
                    "cache_writes": 0, "cache_read_s": 0.0,
                    "nested": 0, "nested_s": 0.0, "analysis_s": 0.0}
            if parent is not None:
                entry["nested"] += 1
                entry["nested_s"] += seconds
            elif st.analysis:
                entry["analysis_s"] += seconds
            else:
                entry[phase + "_s"] += seconds
                if phase == "backend":
                    entry["programs"] += 1
                    for tally, amount in (cache or {}).items():
                        entry[tally] += amount
        reg = default_registry()
        if dropped:
            reg.counter("compile.phases_dropped").add(dropped)
        if parent is not None:
            return   # inside its parent's seconds already
        if st.analysis:
            reg.counter("compile.analysis_seconds").add(seconds)
            return
        capture = st.capture
        if capture is not None and capture["fn"] in (None, name):
            capture[phase + "_s"] = capture.get(phase + "_s", 0.0) + seconds
            if phase == "backend":
                capture["cache"] = _cache_verdict(cache)
        if not mine:
            reg.counter("compile.uninstrumented_seconds").add(seconds)
            return
        reg.counter(f"compile.{phase}_seconds").add(seconds)
        if phase == "backend":
            reg.counter("compile.programs").add()
            if cache:   # by name: lint rule H9 holds the docs' table to these
                reg.counter("compile.cache_requests").add(
                    cache["cache_requests"])
                reg.counter("compile.cache_hits").add(cache["cache_hits"])
                reg.counter("compile.cache_writes").add(
                    cache["cache_writes"])
                reg.counter("compile.cache_read_seconds").add(
                    cache["cache_read_s"])

    def phases(self) -> Dict[str, Dict[str, Any]]:
        """What JAX reported, by function name (oldest first; bounded
        like the event ring, evictions in ``compile.phases_dropped``):
        outermost ``trace_s`` / ``lower_s`` / ``backend_s``, the
        backend events (``programs``) with the persistent cache's
        ``cache_requests`` / ``cache_hits`` / ``cache_misses``
        (requests no hit answered) / ``cache_writes`` /
        ``cache_read_s``, the events that closed inside this name's
        (``nested``, ``nested_s``: part of its seconds, not beside
        them), ``analysis_s`` (``_analyze``'s own compile) and whether
        the name is an instrumented function's."""
        with self._lock:
            return {name: dict(e, cache_misses=(e["cache_requests"]
                                                - e["cache_hits"]))
                    for name, e in self._phases.items()}

    # -- instrumentation -----------------------------------------------------

    def instrument(self, fn: Callable, name: str, kind: str = "jit",
                   config: Optional[dict] = None,
                   arg_names: Optional[Tuple[str, ...]] = None
                   ) -> _LoggedJit:
        """Wrap a jitted callable so its compiles route through this
        log. The wrapper is permanent and cheap disarmed — call sites
        cache it exactly where they cached the raw jit."""
        return _LoggedJit(fn, name, kind, config, arg_names, self)

    def instrument_aot(self, fn: Callable, name: str, kind: str = "aot",
                       wall_s: float = 0.0,
                       detail: Optional[dict] = None) -> "_AotProgram":
        """The executable-import half of the warm-start seam
        (fleet/warmstart.py → ModelFunction.install_aot): wrap a
        DESERIALIZED pre-compiled executable so dispatches route
        through the log's bookkeeping without ever being able to
        record a compile. The load itself lands as an armed-gated
        ``aot_load`` transfer event under ``<name>.aot_load`` — never
        under ``<name>`` itself, because ``compiles_of(<name>)`` is
        the scale-out drill's zero-compile proof and a load must not
        pollute it."""
        if self.armed:
            self.record_transfer(name=f"{name}.aot_load",
                                 kind="aot_load", wall_s=wall_s,
                                 detail=detail or {})
        return _AotProgram(fn, name, kind, self)

    def mark_model_steady(self, model_fn, reason: str = "warmup") -> int:
        """Mark every instrumented program cached on ``model_fn``
        steady (the ``warmup_runner`` / ``RechunkTarget.prewarm``
        hook): from here on, a real compile through any of them is an
        unexpected retrace. Returns how many programs were marked."""
        marked = 0
        for fn in getattr(model_fn, "_jit_cache", {}).values():
            if isinstance(fn, (_LoggedJit, _AotProgram)):
                fn.mark_steady()
                marked += 1
                with self._lock:
                    entry = self._functions.get(fn._name)
                    if entry is not None:
                        entry["steady"] = True
        if marked:
            with self._lock:
                self._steady_models.add(
                    str(getattr(model_fn, "name", "?")))
                n = len(self._steady_models)
            default_registry().gauge("compile.steady_models").set(n)
            logger.debug(
                "compile log: %s marked %d program(s) of %r steady",
                reason, marked, getattr(model_fn, "name", "?"))
        return marked

    # -- recording -----------------------------------------------------------

    def _analyze(self, w: _LoggedJit, args, kwargs
                 ) -> Tuple[Optional[dict], Optional[dict],
                            Optional[str], Optional[Dict[str, str]]]:
        """``cost_analysis()`` / ``memory_analysis()``, the name and
        the instruction-to-scope map (:func:`instruction_scopes`) of
        the program just compiled, via one AOT ``lower().compile()`` (rides
        the persistent XLA compilation cache where configured). Every
        rung degrades to ``None`` — CPU builds that return nothing,
        shapes the AOT path rejects, backends without the API."""
        if not self.analysis_enabled:
            return None, None, None, None
        lower = getattr(w._fn, "lower", None)
        if lower is None:
            return None, None, None, None
        # what JAX reports of this second compile is the
        # instrumentation's own cost (compile.analysis_seconds), not
        # the program's set-up
        st = self._thread()
        st.analysis = True
        try:
            compiled = lower(*args, **kwargs).compile()
        except Exception as e:
            logger.debug("compile log: AOT analysis unavailable for "
                         "%s (%s)", w._name, e)
            return None, None, None, None
        finally:
            st.analysis = False
        cost: Optional[dict] = None
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if isinstance(ca, dict):
                flops = ca.get("flops")
                accessed = ca.get("bytes accessed")
                cost = {
                    "flops": float(flops)
                    if isinstance(flops, (int, float)) else None,
                    "bytes_accessed": float(accessed)
                    if isinstance(accessed, (int, float)) else None,
                }
        except Exception as e:
            default_registry().counter(
                "compile.analysis_degrades").add()
            logger.debug("compile log: cost_analysis unavailable for "
                         "%s (%s)", w._name, e)
        memory: Optional[dict] = None
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                memory = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "generated_code_bytes": int(
                        ma.generated_code_size_in_bytes),
                }
        except Exception as e:
            default_registry().counter(
                "compile.analysis_degrades").add()
            logger.debug("compile log: memory_analysis unavailable "
                         "for %s (%s)", w._name, e)
        module: Optional[str] = None
        scopes: Optional[Dict[str, str]] = None
        as_text = getattr(compiled, "as_text", None)
        if as_text is not None:
            try:
                module, scopes = instruction_scopes(as_text() or "")
            except Exception as e:
                default_registry().counter(
                    "compile.analysis_degrades").add()
                logger.debug("compile log: as_text unavailable for %s "
                             "(%s)", w._name, e)
        return cost, memory, module, scopes

    def _record_compile(self, w: _LoggedJit, args, kwargs, sig, key,
                        wall_s: float, t0: float, t_end: float,
                        verified: bool,
                        prev_signature: Optional[Dict[str, str]] = None,
                        phases: Optional[dict] = None
                        ) -> CompileEvent:
        t_analysis = time.perf_counter()
        cost, memory, module, scopes = self._analyze(w, args, kwargs)
        default_registry().counter("compile.analysis_wall_seconds").add(
            time.perf_counter() - t_analysis)
        if cost and cost.get("flops"):
            w._flops_by_key[key] = cost["flops"]
        w.last_flops = w._flops_by_key.get(key)
        return self.record(
            name=w._name, kind=w._kind, signature=sig,
            config=w._config, wall_s=wall_s, steady=w.steady,
            cost=cost, memory=memory, module=module, scopes=scopes,
            verified=verified,
            span_t0=t0, span_end=t_end,
            prev_signature=prev_signature, table_fallback=False,
            phases=phases)

    def record(self, *, name: str, kind: str, signature: Dict[str, str],
               config: Optional[dict] = None, wall_s: float = 0.0,
               steady: bool = False, cost: Optional[dict] = None,
               memory: Optional[dict] = None,
               module: Optional[str] = None,
               scopes: Optional[Dict[str, str]] = None,
               verified: bool = True,
               span_t0: Optional[float] = None,
               span_end: Optional[float] = None,
               prev_signature: Optional[Dict[str, str]] = None,
               retraceable: bool = True,
               table_fallback: bool = True,
               phases: Optional[dict] = None) -> CompileEvent:
        """Record one compile event (the instrumented wrappers call
        this; ``deserialize``/``device_params`` record their transfer-
        shaped events directly). Computes retrace/unexpected verdicts
        — a retrace diffs against ``prev_signature`` (the wrapper's
        most recently seen other signature, which covers shapes warmed
        while disarmed) or the function table's last recorded one;
        ``steady`` makes ANY real compile unexpected (a steady program
        compiled its warm shapes already — new compiles are exactly
        what the guarantee forbids) — publishes the ``compile.*``
        counters, and on an unexpected retrace escalates: ERROR log
        with the diff, flight dump (armed recorders only)."""
        reg = default_registry()
        with self._lock:
            entry = self._functions.get(name)
            if entry is None:
                entry = self._functions[name] = {
                    "kind": kind, "compiles": 0, "retraces": 0,
                    "unexpected": 0, "wall_s": 0.0,
                    "signatures": [], "flops": None, "steady": False}
            prev_sigs: List[Dict[str, str]] = entry["signatures"]
            prev = prev_signature
            if prev is None and table_fallback and prev_sigs:
                # direct record() callers diff against the per-NAME
                # history; wrapper-routed compiles pass
                # table_fallback=False — each wrapper's own seen set
                # is its history, so a FRESH same-name model's first
                # compile (a redeploy/hot-swap) is a first compile,
                # never a phantom retrace with an empty diff
                prev = prev_sigs[-1]
            if not retraceable:
                # transfer-shaped events (device_params placements,
                # deserialize) repeat per cache key by design — a
                # repeat is NOT a recompile and must not inflate
                # compile.retraces or fabricate an empty diff
                prev = None
            retrace = prev is not None
            unexpected = steady
            diff = (signature_diff(prev, signature)
                    if prev is not None else None)
            entry["compiles"] += 1
            entry["wall_s"] += wall_s
            entry["steady"] = steady
            if retrace:
                entry["retraces"] += 1
                self.retraces += 1
            if unexpected:
                entry["unexpected"] += 1
                self.unexpected_retraces += 1
            if cost and cost.get("flops"):
                entry["flops"] = cost["flops"]
            prev_sigs.append(dict(signature))
            del prev_sigs[:-SIGNATURES_PER_FUNCTION]
            self.events_total += 1
            seq = self.events_total
            evicting = len(self._ring) == self._ring.maxlen
            if evicting:
                self.dropped += 1
            event = CompileEvent(
                seq=seq, name=name, kind=kind,
                signature=dict(signature), config=dict(config or {}),
                wall_s=wall_s, retrace=retrace, unexpected=unexpected,
                diff=diff, cost=cost, memory=memory, verified=verified,
                t_s=round(time.perf_counter() - self._epoch, 4),
                module=module, scopes=scopes,
                **{k: (phases or {}).get(k)
                   for k in ("trace_s", "lower_s", "backend_s", "cache")})
            self._ring.append(event)
            n_functions = len(self._functions)
        reg.counter("compile.events").add()
        reg.counter("compile.wall_seconds").add(wall_s)
        reg.gauge("compile.functions").set(n_functions)
        if retrace:
            reg.counter("compile.retraces").add()
        if evicting:
            # the bounded ring evicts its oldest event — counted,
            # never silent (the tracer drop-note discipline)
            reg.counter("compile.events_dropped").add()
        # the compile lane span (the timed_device_get _record
        # precedent: verdicts are only known after the call, so the
        # span is stamped post-hoc from the same clock reads)
        trc = tracer()
        if trc.armed and span_t0 is not None and span_end is not None:
            trc._record("compile", "compile", span_t0, span_end, {
                "fn": name, "kind": kind, "retrace": retrace,
                "unexpected": unexpected, "diff": (diff or "")[:400],
                "flops": (cost or {}).get("flops"),
            })
        if unexpected:
            reg.counter("compile.unexpected_retraces").add()
            logger.error(
                "UNEXPECTED RETRACE of steady program %s (%.3fs "
                "compile on the hot path): %s — the warm-start "
                "guarantee (docs/SERVING.md) was violated; the shape "
                "ladder/warmup does not cover this signature", name,
                wall_s, diff or "(first observed signature)")
            self._fire_flight(name, diff)
        return event

    def _fire_flight(self, name: str, diff: Optional[str]) -> None:
        """The unexpected-retrace flight trigger: dump only when the
        recorder is armed (the ``record_failure`` discipline — a
        disarmed process must not start writing files), degrade on any
        probe failure (the dump is forensics, not control flow)."""
        try:
            from sparkdl_tpu.obs import flight
            rec = flight.recorder()
            if rec.armed:
                rec.dump(reason=f"unexpected retrace of {name}: "
                                f"{(diff or '?')[:300]}")
        # sparkdl-lint: allow[H12] -- the retrace itself is already accounted (compile.unexpected_retraces counted + ERROR-logged before this dump attempt); the dump is forensics on top, and its failure is logged loudly here
        except Exception:
            logger.exception(
                "compile log: flight dump for the unexpected retrace "
                "of %s failed (the retrace is already counted in "
                "compile.unexpected_retraces and logged above)", name)

    def record_transfer(self, *, name: str, kind: str, wall_s: float,
                        detail: Optional[dict] = None) -> None:
        """The non-jit events the forensics still want on the books:
        ``device_params`` weight placements and StableHLO
        ``deserialize`` (kind names which). Armed-gated by the caller;
        never retraces (each is a one-shot per cache key —
        ``retraceable=False`` keeps repeats out of the retrace
        counters)."""
        self.record(name=name, kind=kind,
                    signature={k: str(v)
                               for k, v in (detail or {}).items()},
                    wall_s=wall_s, steady=False, verified=True,
                    retraceable=False)

    # -- readout -------------------------------------------------------------

    def events(self) -> List[CompileEvent]:
        """The retained events, oldest first (bounded ring)."""
        with self._lock:
            return list(self._ring)

    def events_for(self, name: str) -> List[CompileEvent]:
        with self._lock:
            return [e for e in self._ring if e.name == name]

    def compiles_of(self, name: str) -> int:
        """Lifetime compiles of one function name (survives ring
        eviction — the per-function table is not the ring)."""
        with self._lock:
            entry = self._functions.get(name)
            return int(entry["compiles"]) if entry else 0

    def state(self) -> Dict[str, Any]:
        """ONE shape shared by ``/statusz`` and flight bundles, so a
        curl and a postmortem never disagree."""
        with self._lock:
            functions = {
                name: {"kind": e["kind"], "compiles": e["compiles"],
                       "retraces": e["retraces"],
                       "unexpected": e["unexpected"],
                       "wall_s": round(e["wall_s"], 4),
                       "flops": e["flops"], "steady": e["steady"]}
                for name, e in sorted(self._functions.items())}
            last = self._ring[-1] if self._ring else None
            state = {
                "armed": self.armed,
                "capacity": self.capacity,
                "events": self.events_total,
                "retained": len(self._ring),
                "dropped": self.dropped,
                "retraces": self.retraces,
                "unexpected_retraces": self.unexpected_retraces,
                "steady_models": sorted(self._steady_models),
                "wall_seconds_total": round(
                    sum(e["wall_s"] for e in self._functions.values()),
                    4),
                "functions": functions,
            }
        state["last_event"] = (
            {"name": last.name, "kind": last.kind,
             "wall_s": round(last.wall_s, 4), "retrace": last.retrace,
             "unexpected": last.unexpected, "diff": last.diff}
            if last is not None else None)
        return state

    def clear(self) -> None:
        """Drop every event and per-function table (test isolation);
        counters in the registry are not rewound (monotonic)."""
        with self._lock:
            self._ring.clear()
            self._functions.clear()
            self._steady_models.clear()
            self._phases.clear()
            self.events_total = 0
            self.dropped = 0
            self.retraces = 0
            self.unexpected_retraces = 0
            self.phases_dropped = 0

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        # the lock, event ring, and per-function tables are
        # process-local observations; capacity and the armed-ness
        # override travel
        state = self.__dict__.copy()
        del state["_lock"]
        del state["_ring"]
        del state["_functions"]
        del state["_steady_models"]
        del state["_epoch"]
        del state["_names"]
        del state["_phases"]
        del state["_heard"]
        state["phases_dropped"] = 0
        state["events_total"] = 0
        state["dropped"] = 0
        state["retraces"] = 0
        state["unexpected_retraces"] = 0
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=self.capacity)
        self._functions = {}
        self._steady_models = set()
        self._epoch = time.perf_counter()
        self._names = set()
        self._phases = {}
        self._heard = threading.local()


def _cache_verdict(cache: Optional[dict]) -> str:
    """A backend event's ``cache``: ``hit``, ``miss`` (asked, and no
    hit answered) or ``off`` (the persistent cache was not asked)."""
    if not cache or not cache["cache_requests"]:
        return "off"
    return "hit" if cache["cache_hits"] else "miss"


_COMPILE_LOG = CompileLog()
_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def compile_log() -> CompileLog:
    """THE process-wide compile log every package jit compile routes
    through (one attribution table is the whole point)."""
    return _COMPILE_LOG


def listen() -> None:
    """Subscribe THE log to ``jax.monitoring``, once a process (module
    docstring, "The phases"). It imports jax, so it is called where
    jax is there already: by ``graph/function.py`` at its import and
    by every ``instrument``. A process that never builds a program (a
    decode worker) never pays the import."""
    global _LISTENING
    if _LISTENING:
        return
    with _LISTEN_LOCK:
        if _LISTENING:
            return
        from jax import monitoring
        reg = default_registry()
        for name in PHASE_COUNTERS:
            reg.counter(name)
        monitoring.register_scalar_listener(_COMPILE_LOG._on_start)
        monitoring.register_event_listener(_COMPILE_LOG._on_event)
        monitoring.register_event_duration_secs_listener(
            _COMPILE_LOG._on_duration)
        _LISTENING = True


# -- HBM accounting -----------------------------------------------------------

def publish_hbm(registry=None) -> int:
    """Per-device ``memory_stats()`` promoted to live ``hbm.*`` gauges
    with high-watermark tracking: ``hbm.d<i>.bytes_in_use`` /
    ``.bytes_limit`` / ``.peak_bytes_in_use`` per device plus the
    cross-device ``hbm.bytes_in_use`` total and its lifetime
    ``hbm.bytes_in_use_peak``. Returns how many devices reported;
    CPU devices typically report nothing and
    ``hbm.devices_reporting`` says 0 rather than the lane going
    missing. Called per ledger window (obs/ledger.py), per
    ``/metricsz`` scrape, and per flight bundle — periodic wherever a
    reader already is, never a thread of its own."""
    reg = registry if registry is not None else default_registry()
    try:
        import jax
        devices = jax.devices()
    except Exception as e:
        logger.debug("hbm accounting: no backend (%s)", e)
        reg.gauge("hbm.devices_reporting").set(0)
        return 0
    reporting = 0
    total = 0.0
    for i, d in enumerate(devices):
        probe = getattr(d, "memory_stats", None)
        try:
            stats = probe() if probe is not None else None
        except Exception as e:
            logger.debug("hbm accounting: memory_stats failed on %s "
                         "(%s)", d, e)
            stats = None
        if not isinstance(stats, dict):
            continue
        reporting += 1
        in_use = stats.get("bytes_in_use")
        if isinstance(in_use, (int, float)):
            reg.gauge(f"hbm.d{i}.bytes_in_use").set(in_use)
            reg.gauge(f"hbm.d{i}.peak_bytes_in_use").set_max(in_use)
            total += in_use
        limit = stats.get("bytes_limit")
        if isinstance(limit, (int, float)):
            reg.gauge(f"hbm.d{i}.bytes_limit").set(limit)
        # a backend-reported peak outranks our sampled watermark
        peak = stats.get("peak_bytes_in_use")
        if isinstance(peak, (int, float)):
            reg.gauge(f"hbm.d{i}.peak_bytes_in_use").set_max(peak)
    reg.gauge("hbm.devices_reporting").set(reporting)
    if reporting:
        reg.gauge("hbm.bytes_in_use").set(total)
        reg.gauge("hbm.bytes_in_use_peak").set_max(total)
    return reporting
