"""The six sparkdl-lint rules (H1–H6), each an AST pass.

Every rule is a function ``(tree, path) -> list[Finding]`` registered
in :data:`RULES`; the walker runs all of them per file and then applies
suppressions. Rules track the dotted ``Class.method`` qualname of each
hit so the allowlist can scope to a single function.

These are HEURISTIC checks tuned to this repo's idioms — they resolve
names lexically, not by type inference. The contract is: zero false
negatives on the patterns the repo actually writes (the fixtures in
``tests/test_analysis.py`` pin them), and any false positive is cheap
to suppress inline WITH a justification, which is itself documentation.
"""

from __future__ import annotations

import ast
import os
from typing import Callable, Dict, List, Optional, Set, Tuple

from sparkdl_tpu.analysis.findings import Finding

# ---------------------------------------------------------------------------
# shared helpers


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _ScopedVisitor(ast.NodeVisitor):
    """NodeVisitor that maintains the dotted Class.method qualname."""

    def __init__(self, path: str):
        self.path = path
        self.findings: List[Finding] = []
        self._stack: List[str] = []

    @property
    def qualname(self) -> str:
        return ".".join(self._stack)

    def _push(self, name: str, node: ast.AST):
        self._stack.append(name)
        self.generic_visit(node)
        self._stack.pop()

    def visit_ClassDef(self, node: ast.ClassDef):
        self._push(node.name, node)

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self._push(node.name, node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef):
        self._push(node.name, node)

    def flag(self, rule: str, node: ast.AST, message: str):
        self.findings.append(Finding(
            rule=rule, path=self.path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=message, qualname=self.qualname))


# ---------------------------------------------------------------------------
# H1 — implicit host transfers on the ship path

_H1_DEVICE_GET = {"jax.device_get", "jax.block_until_ready"}
_H1_NP_WRAP = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_H1_DEVICE_PRODUCERS = ("jnp.", "jax.numpy.", "jax.")


class _H1Transfers(_ScopedVisitor):
    """Host-transfer syncs outside the drain path. Each of these blocks
    the calling thread until the device catches up — the exact stall
    the runner's in-flight window exists to hide."""

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        if name in _H1_DEVICE_GET:
            self.flag(
                "H1", node,
                f"`{name}` forces a device→host sync; only the "
                "allowlisted drain path (SlabSink.write, measure "
                "tools) may block on the device — route results "
                "through the runner's sink, or suppress with "
                "`# sparkdl-lint: allow[H1] -- <why this drain is "
                "legitimate>`")
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"):
            self.flag(
                "H1", node,
                "`.block_until_ready()` forces a device sync; suppress "
                "with `# sparkdl-lint: allow[H1] -- <why>` if this "
                "drain is deliberate")
        elif name in _H1_NP_WRAP and node.args:
            inner = node.args[0]
            if isinstance(inner, ast.Call):
                producer = _dotted(inner.func)
                if producer and producer.startswith(_H1_DEVICE_PRODUCERS):
                    self.flag(
                        "H1", node,
                        f"`{name}(...)` over a `{producer}` result "
                        "implicitly copies device memory to host; "
                        "keep device values device-resident or drain "
                        "them through the runner sink (suppress: "
                        "`# sparkdl-lint: allow[H1] -- <why>`)")
        self.generic_visit(node)


def check_h1(tree: ast.AST, path: str) -> List[Finding]:
    v = _H1Transfers(path)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# H2 — jit / retrace hazards

_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit",
              "jax.experimental.pjit.pjit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}
_H2_SIDE_EFFECT_PREFIXES = ("time.", "np.random.", "numpy.random.",
                            "random.")
_H2_SIDE_EFFECT_CALLS = {"print", "input"}
# obs tracing spans read the host wall clock (time.perf_counter) on
# enter/exit — inside a traced function that happens ONCE, at trace
# time, freezing compile-time timestamps into the program and recording
# nothing per step. Matches `span(...)` and any `<obj>.span(...)`.
_H2_TRACE_SPAN = "span"
_STATIC_KWARGS = {"static_argnums", "static_argnames"}


def _jit_target_of(call: ast.Call) -> Optional[ast.Call]:
    """The jit-ish Call, unwrapping ``partial(jax.jit, ...)``."""
    name = _dotted(call.func)
    if name in _JIT_NAMES:
        return call
    if name in _PARTIAL_NAMES and call.args:
        inner = _dotted(call.args[0])
        if inner in _JIT_NAMES:
            return call
    return None


def _is_jit_decorator(dec: ast.AST) -> bool:
    if _dotted(dec) in _JIT_NAMES:
        return True
    return isinstance(dec, ast.Call) and _jit_target_of(dec) is not None


class _H2SideEffects(ast.NodeVisitor):
    """Scans the BODY of a traced function: anything here runs at trace
    time, once per compilation — wall-clock reads read compile time,
    prints fire once then vanish, stateful RNG freezes one sample into
    the compiled program."""

    def __init__(self, outer: "_H2Retrace", qualname: str):
        self.outer = outer
        self.qualname = qualname

    def _flag(self, node: ast.AST, message: str):
        self.outer.findings.append(Finding(
            rule="H2", path=self.outer.path, line=node.lineno,
            col=node.col_offset, message=message,
            qualname=self.qualname))

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        if name in _H2_SIDE_EFFECT_CALLS:
            self._flag(node, (
                f"`{name}(...)` inside a jit-traced function executes "
                "at TRACE time only (use jax.debug.print for per-step "
                "output); suppress: `# sparkdl-lint: allow[H2] -- "
                "<why>`"))
        elif name and (name == _H2_TRACE_SPAN
                       or name.endswith("." + _H2_TRACE_SPAN)):
            self._flag(node, (
                f"`{name}(...)` inside a jit-traced function: obs "
                "spans read the host wall clock at TRACE time — the "
                "compiled program would carry one frozen timestamp "
                "and record nothing per step; trace around the jit "
                "call, not inside it (suppress: `# sparkdl-lint: "
                "allow[H2] -- <why>`)"))
        elif name and name.startswith(_H2_SIDE_EFFECT_PREFIXES):
            if name.startswith("time."):
                why = ("reads trace-time wall clock, frozen into the "
                       "compiled program — time OUTSIDE the jit")
            else:
                why = ("stateful host RNG samples ONCE at trace time; "
                       "thread a jax.random key instead")
            self._flag(node, (
                f"`{name}(...)` inside a jit-traced function: {why} "
                "(suppress: `# sparkdl-lint: allow[H2] -- <why>`)"))
        self.generic_visit(node)

    # a nested def/lambda inside a jitted fn is traced too — keep
    # walking (generic_visit covers them)


class _H2Retrace(_ScopedVisitor):
    def __init__(self, path: str, module_defs: Dict[str, ast.AST]):
        super().__init__(path)
        self._module_defs = module_defs
        self._checked: Set[int] = set()

    def _scan_traced(self, fn_node: ast.AST, qualname: str):
        if id(fn_node) in self._checked:
            return
        self._checked.add(id(fn_node))
        body = (fn_node.body if isinstance(fn_node.body, list)
                else [fn_node.body])  # Lambda body is a single expr
        scanner = _H2SideEffects(self, qualname)
        for stmt in body:
            scanner.visit(stmt)

    def _check_static_kwargs(self, call: ast.Call):
        for kw in call.keywords:
            if kw.arg in _STATIC_KWARGS and isinstance(
                    kw.value, (ast.List, ast.Set, ast.Dict,
                               ast.ListComp, ast.SetComp, ast.DictComp)):
                self.flag(
                    "H2", kw.value,
                    f"`{kw.arg}` given a mutable literal: static args "
                    "are compilation-cache KEYS — spell it as an int "
                    "or tuple literal so hashability is visible at the "
                    "call site (suppress: `# sparkdl-lint: allow[H2] "
                    "-- <why>`)")

    def visit_FunctionDef(self, node: ast.FunctionDef):
        if any(_is_jit_decorator(d) for d in node.decorator_list):
            self._scan_traced(node, ".".join(self._stack + [node.name]))
        self._push(node.name, node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call):
        jit_call = _jit_target_of(node)
        if jit_call is not None:
            self._check_static_kwargs(node)
            # jax.jit(f) / partial(jax.jit, ...)(f): resolve f when it
            # is a lambda or a same-module def
            args = node.args
            if _dotted(node.func) in _PARTIAL_NAMES:
                args = args[1:]
            for arg in args:
                if isinstance(arg, ast.Lambda):
                    self._scan_traced(arg, self.qualname or "<lambda>")
                elif isinstance(arg, ast.Name):
                    target = self._module_defs.get(arg.id)
                    if target is not None:
                        self._scan_traced(target, arg.id)
        self.generic_visit(node)


def check_h2(tree: ast.AST, path: str) -> List[Finding]:
    # name → def map for resolving jax.jit(fn_name); last def wins,
    # names defined more than once with different nodes still resolve
    # (both get scanned only if both are passed to jit)
    defs: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
    v = _H2Retrace(path, defs)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# H3 — concurrency discipline

# Condition counts: it wraps (or owns) a mutex, so a class keeping one
# per instance has exactly the same pickle problem as a raw Lock — the
# serve layer's RequestQueue is the canonical case.
_LOCK_CTORS = {"threading.Lock", "threading.RLock", "Lock", "RLock",
               "threading.Condition", "Condition"}
_PICKLE_HOOKS = {"__getstate__", "__reduce__", "__reduce_ex__"}
_H3_EXEMPT_METHODS = {"__init__", "__post_init__", "__new__",
                      "__setstate__", "__getstate__"}


def _is_lock_ctor(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _dotted(node.func) in _LOCK_CTORS)


def _instance_lock_attrs(cls: ast.ClassDef) -> List[Tuple[str, int]]:
    """``self.X = threading.Lock()`` assignments in methods, plus
    dataclass ``field(default_factory=threading.Lock)`` declarations —
    both become per-INSTANCE lock state that pickle chokes on (class-
    body ``_lock = Lock()`` attributes are class state and exempt)."""
    out: List[Tuple[str, int]] = []
    for item in cls.body:
        if isinstance(item, ast.AnnAssign) and isinstance(
                item.value, ast.Call):
            fn = _dotted(item.value.func)
            if fn in ("field", "dataclasses.field"):
                for kw in item.value.keywords:
                    if kw.arg == "default_factory" and \
                            _dotted(kw.value) in _LOCK_CTORS:
                        name = (item.target.id if isinstance(
                            item.target, ast.Name) else "?")
                        out.append((name, item.lineno))
        elif isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for node in ast.walk(item):
                if isinstance(node, ast.Assign) and _is_lock_ctor(
                        node.value):
                    for tgt in node.targets:
                        if (isinstance(tgt, ast.Attribute)
                                and isinstance(tgt.value, ast.Name)
                                and tgt.value.id == "self"):
                            out.append((tgt.attr, node.lineno))
    return out


def _guarded_fields(cls: ast.ClassDef) -> Tuple[Set[str], str]:
    """The ``_lock_guards = ("field", ...)`` declaration: instance
    fields whose WRITES must hold ``self._lock``. Returns (fields,
    lock attr name) — the guarding lock is ``_lock`` by convention."""
    for item in cls.body:
        if isinstance(item, ast.Assign):
            for tgt in item.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "_lock_guards":
                    if isinstance(item.value, (ast.Tuple, ast.List)):
                        return ({e.value for e in item.value.elts
                                 if isinstance(e, ast.Constant)
                                 and isinstance(e.value, str)}, "_lock")
    return (set(), "_lock")


def _with_holds_lock(node: ast.With, lock_attr: str) -> bool:
    for item in node.items:
        ctx = item.context_expr
        if (isinstance(ctx, ast.Attribute) and ctx.attr == lock_attr
                and isinstance(ctx.value, ast.Name)
                and ctx.value.id == "self"):
            return True
    return False


class _H3Concurrency(_ScopedVisitor):
    def visit_ClassDef(self, node: ast.ClassDef):
        locks = _instance_lock_attrs(node)
        if locks:
            hooks = {item.name for item in node.body
                     if isinstance(item, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))}
            if not (hooks & _PICKLE_HOOKS):
                attrs = ", ".join(sorted({a for a, _ in locks}))
                self._stack.append(node.name)
                self.findings.append(Finding(
                    rule="H3", path=self.path, line=node.lineno,
                    col=node.col_offset, qualname=self.qualname,
                    message=(
                        f"class holds threading lock(s) [{attrs}] but "
                        "defines no __getstate__/__reduce__ — locks "
                        "don't pickle, and stage closures ship to "
                        "Spark executors (see "
                        "RunnerMetrics.__getstate__ for the drop-and-"
                        "recreate discipline); suppress: "
                        "`# sparkdl-lint: allow[H3] -- <why>`")))
                self._stack.pop()
        guards, lock_attr = _guarded_fields(node)
        if guards:
            for item in node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and item.name not in _H3_EXEMPT_METHODS:
                    self._stack.append(node.name)
                    self._stack.append(item.name)
                    self._check_guarded(item, guards, lock_attr,
                                        in_lock=False)
                    self._stack.pop()
                    self._stack.pop()
        self._push(node.name, node)

    def _check_guarded(self, node: ast.AST, guards: Set[str],
                       lock_attr: str, in_lock: bool):
        for child in ast.iter_child_nodes(node):
            child_in_lock = in_lock
            if isinstance(child, ast.With) and _with_holds_lock(
                    child, lock_attr):
                child_in_lock = True
            if isinstance(child, (ast.Assign, ast.AugAssign)) \
                    and not child_in_lock:
                targets = (child.targets
                           if isinstance(child, ast.Assign)
                           else [child.target])
                for tgt in targets:
                    if (isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"
                            and tgt.attr in guards):
                        self.flag(
                            "H3", child,
                            f"write to `self.{tgt.attr}` — declared "
                            f"lock-guarded by `_lock_guards` — outside "
                            f"a `with self.{lock_attr}` block "
                            "(suppress: `# sparkdl-lint: allow[H3] "
                            "-- <why>`)")
            self._check_guarded(child, guards, lock_attr, child_in_lock)


def check_h3(tree: ast.AST, path: str) -> List[Finding]:
    v = _H3Concurrency(path)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# H4 — quiesce hygiene

_CLEANUP_TOKENS = ("close", "cleanup", "quiesce", "shutdown", "stop",
                   "release", "teardown", "__exit__", "__del__",
                   "drain")


def _is_cleanup_name(name: str) -> bool:
    low = name.lower()
    return any(tok in low for tok in _CLEANUP_TOKENS)


def _swallows(handler: ast.ExceptHandler) -> bool:
    """Body is only ``pass`` / ``...`` — the exception vanishes."""
    for stmt in handler.body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(
                stmt.value, ast.Constant):
            continue  # docstring/ellipsis placeholder
        return False
    return True


class _H4Quiesce(_ScopedVisitor):
    def __init__(self, path: str):
        super().__init__(path)
        self._finally_depth = 0

    def visit_Try(self, node: ast.Try):
        for part in (node.body, node.orelse):
            for stmt in part:
                self.visit(stmt)
        for handler in node.handlers:
            self._check_handler(handler)
            self.visit(handler)
        self._finally_depth += 1
        for stmt in node.finalbody:
            self.visit(stmt)
        self._finally_depth -= 1

    def visit_TryStar(self, node):  # pragma: no cover - py3.11 syntax
        self.visit_Try(node)

    def _check_handler(self, handler: ast.ExceptHandler):
        if handler.type is None:
            self.flag(
                "H4", handler,
                "bare `except:` also swallows KeyboardInterrupt/"
                "SystemExit — a quiesce that can't be interrupted "
                "hangs the engine's drain on shutdown; catch "
                "`Exception` (and log it) instead (suppress: "
                "`# sparkdl-lint: allow[H4] -- <why>`)")
            return
        if _swallows(handler) and (self._finally_depth > 0
                                   or _is_cleanup_name(self.qualname)):
            self.flag(
                "H4", handler,
                "silently swallowed exception in a cleanup/quiesce "
                "path: a secondary failure here masks whether the "
                "drain actually ran (the effectful-source contract) — "
                "log it at debug level at minimum (suppress: "
                "`# sparkdl-lint: allow[H4] -- <why>`)")

    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        # reached only for handlers nested inside other visited bodies
        # (visit_Try dispatches its own handlers through _check_handler
        # before descending)
        self.generic_visit(node)


def check_h4(tree: ast.AST, path: str) -> List[Finding]:
    v = _H4Quiesce(path)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# H5 — wall-clock reads in the observability/serving timing paths

# The tracer's whole premise is ONE clock (time.perf_counter from a
# single epoch): every span, latency reservoir sample, deadline, and
# watchdog beat in obs/ and serve/ must come off it. time.time() /
# datetime.now() are wall clocks — NTP steps them, they jump across
# suspend, and mixing them with perf_counter intervals silently skews
# exactly the numbers this layer exists to make trustworthy. The rule
# is PATH-scoped: wall-clock reads elsewhere (bench stamps, file
# mtimes) are fine.
_H5_BANNED = {
    "time.time": "time.perf_counter()",
    "datetime.now": "time.perf_counter()",
    "datetime.utcnow": "time.perf_counter()",
    "datetime.datetime.now": "time.perf_counter()",
    "datetime.datetime.utcnow": "time.perf_counter()",
}
_H5_PATHS = ("sparkdl_tpu/obs/", "sparkdl_tpu/serve/")


class _H5Clock(_ScopedVisitor):
    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        if name in _H5_BANNED:
            self.flag(
                "H5", node,
                f"`{name}()` in the obs/serve timing layer: span and "
                "latency math must share the tracer's monotonic clock "
                f"— use {_H5_BANNED[name]} (wall time jumps with NTP/"
                "suspend and silently skews the one timeline this "
                "layer exists to keep honest); a genuine wall-clock "
                "need (a human-readable artifact stamp) suppresses: "
                "`# sparkdl-lint: allow[H5] -- <why>`")
        self.generic_visit(node)


def check_h5(tree: ast.AST, path: str) -> List[Finding]:
    if not _path_in(path, _H5_PATHS):
        return []
    v = _H5Clock(path)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# H6 — metric-name cardinality (request ids must never become keys)

# The registry is a name → metric table that lives for the process and
# renders every entry to /metricsz on each scrape. A metric NAME built
# from a per-request identifier therefore grows without bound (one
# request = one eternal registry entry + one Prometheus series) — the
# classic cardinality explosion that kills a metrics backend. The
# per-request layer has purpose-built homes for these values instead:
# the bounded RequestLog, reservoir exemplars, and span args
# (obs/request_log.py). The rule is lexical, matching this repo's
# idiom: a registry factory call whose name expression interpolates a
# request-shaped identifier.

_H6_METRIC_FACTORIES = {"counter", "gauge", "reservoir"}
_H6_REQUEST_NAMES = {"request_id", "req_id", "rid"}


def _h6_request_ident(expr: ast.AST) -> Optional[str]:
    """The first request-shaped identifier used inside a metric-name
    expression, or None. Matches bare names (``rid``), attribute tails
    (``req.rid``, ``record.request_id``), and anything whose name ends
    in ``request_id``."""
    for node in ast.walk(expr):
        name: Optional[str] = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        if name is None:
            continue
        low = name.lower()
        if low in _H6_REQUEST_NAMES or low.endswith("request_id"):
            return name
    return None


class _H6Cardinality(_ScopedVisitor):
    def visit_Call(self, node: ast.Call):
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _H6_METRIC_FACTORIES:
            # the metric name: first positional, or the name= kwarg —
            # the keyword spelling is just as legal a call form
            name_arg = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords
                 if kw.arg == "name"), None)
            if name_arg is not None \
                    and not isinstance(name_arg, ast.Constant):
                ident = _h6_request_ident(name_arg)
                if ident is not None:
                    self.flag(
                        "H6", node,
                        f"metric name built from `{ident}`: a "
                        "per-request id as a registry key grows one "
                        "eternal metric (and Prometheus series) PER "
                        "REQUEST — unbounded cardinality. Request ids "
                        "belong in the bounded RequestLog, reservoir "
                        "exemplars, or span args "
                        "(obs/request_log.py), never in metric names "
                        "(suppress: `# sparkdl-lint: allow[H6] -- "
                        "<why this key set is bounded>`)")
        self.generic_visit(node)


def check_h6(tree: ast.AST, path: str) -> List[Finding]:
    v = _H6Cardinality(path)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# H12 — exception-flow accounting (serve/obs/runtime hot paths)

# PR 7's population-separation fix established the invariant: every
# failure on a serving/observability hot path must LAND somewhere an
# operator can see — a failure counter, an SLO outcome, a re-raise, a
# recorded error field. An `except` that swallows (pass, bare
# continue, or log-only: logs rotate away, counters don't) breaks the
# accounting chain that makes `serve.failures`, the availability burn
# rate, and the flight recorder's triggers trustworthy. The rule is
# PATH-scoped to the hot paths; swallows elsewhere stay H4's
# (cleanup-path) business.

_H12_PATHS = ("sparkdl_tpu/serve/", "sparkdl_tpu/obs/",
              "sparkdl_tpu/runtime/")
_H12_LOG_NAMES = {"print", "warn_once"}
_H12_LOG_METHODS = {"debug", "info", "warning", "error", "exception",
                    "critical", "log"}


def _h12_is_log_call(call: ast.Call) -> bool:
    name = _dotted(call.func)
    if name in _H12_LOG_NAMES or name == "warnings.warn":
        return True
    if name and name.startswith("logging."):
        return True
    if isinstance(call.func, ast.Attribute) and \
            call.func.attr in _H12_LOG_METHODS:
        recv = call.func.value
        # the chained form: logging.getLogger(__name__).warning(...) —
        # the receiver is a CALL, so _dotted() can't name it
        if isinstance(recv, ast.Call):
            recv_fn = _dotted(recv.func) or ""
            return recv_fn.rsplit(".", 1)[-1] == "getLogger"
        recv_name = (_dotted(recv) or "").lower()
        return "log" in recv_name or recv_name.startswith("warnings")
    return False


def _h12_swallows(handler: ast.ExceptHandler) -> bool:
    """True when every statement in the handler is accounting-free:
    pass / bare continue / docstring / import / a log-only call. Any
    raise, return, assignment (the error lands in state), counter
    ``.inc()``/``.add()``, ``record_failure``, ``set_exception`` —
    anything that BINDS the failure to an observable outcome — makes
    the handler accountable and clean."""
    for stmt in handler.body:
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Import,
                             ast.ImportFrom)):
            continue
        if isinstance(stmt, ast.Expr):
            if isinstance(stmt.value, ast.Constant):
                continue
            if isinstance(stmt.value, ast.Call) and \
                    _h12_is_log_call(stmt.value):
                continue
        return False
    return True


class _H12ExceptionFlow(_ScopedVisitor):
    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        if _h12_swallows(node):
            kind = ("bare `continue`" if any(
                isinstance(s, ast.Continue) for s in node.body)
                else "log-only" if any(
                    isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Call)
                    for s in node.body)
                else "`pass`")
            self.flag(
                "H12", node,
                f"{kind} exception handler on a serve/obs/runtime hot "
                "path: the failure reaches no counter, SLO outcome, "
                "or error state — the accounting chain (serve."
                "failures, availability burn, flight triggers) "
                "silently loses it; record a failure counter/SLO "
                "outcome on the handler path (the PR-7 population-"
                "separation contract), or suppress with "
                "`# sparkdl-lint: allow[H12] -- <why this failure "
                "needs no accounting>`")
        self.generic_visit(node)


def _path_in(path: str, prefixes) -> bool:
    """Is ``path`` inside one of the package-relative ``prefixes``?
    Checked against the path as given AND its absolute form — linting
    ``obs/`` from inside the package dir must not silently skip a
    path-scoped rule."""
    for cand in (path, os.path.abspath(path)):
        norm = cand.replace("\\", "/")
        if any(p in norm for p in prefixes):
            return True
    return False


def check_h12(tree: ast.AST, path: str) -> List[Finding]:
    if not _path_in(path, _H12_PATHS):
        return []
    v = _H12ExceptionFlow(path)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# H13 — unbounded retry loops (serve/runtime/data/resilience paths)

# PR 11's resilience contract: every re-attempt on a hot path runs
# under the shared RetryPolicy — bounded attempts, exponential
# backoff, a retry budget (resilience/policy.py). The shape that
# breaks all three at once is the bare `while True: try/except` whose
# handler swallows AND continues: on sustained failure it spins
# forever, unthrottled, amplifying the load on the exact dependency
# that is already failing. The rule flags an unbounded-test loop
# (`while True` / `while 1`) containing an except handler with no
# escape (no raise/break/return reachable in the handler): on the
# exception path, nothing ever ends the loop. Loops whose handler
# re-raises, breaks, or returns — including RetryPolicy.call, whose
# handler re-raises on grant() refusal — are clean by construction.

_H13_PATHS = ("sparkdl_tpu/serve/", "sparkdl_tpu/runtime/",
              "sparkdl_tpu/data/", "sparkdl_tpu/resilience/")

_H13_SCOPE_STOPS = (ast.FunctionDef, ast.AsyncFunctionDef,
                    ast.ClassDef, ast.Lambda)


def _h13_unbounded(node: ast.While) -> bool:
    return isinstance(node.test, ast.Constant) \
        and node.test.value in (True, 1)


def _h13_handlers(stmts, out: List[ast.ExceptHandler]) -> None:
    """Except handlers whose swallow retries THIS unbounded loop:
    everything reachable in its body except nested defs (a callback's
    control flow is the callee's) and nested unbounded whiles (their
    own visit). Nested BOUNDED loops (for / `while cond`) descend —
    a per-iteration-bounded inner loop still re-enters the outer
    `while True` forever when its handler swallows."""
    for s in stmts:
        if isinstance(s, _H13_SCOPE_STOPS):
            continue
        if isinstance(s, ast.While) and _h13_unbounded(s):
            continue
        if isinstance(s, ast.Try):
            out.extend(s.handlers)
            _h13_handlers(s.body, out)
            _h13_handlers(s.orelse, out)
            _h13_handlers(s.finalbody, out)
            for h in s.handlers:
                _h13_handlers(h.body, out)
        elif isinstance(s, (ast.If, ast.While)):
            _h13_handlers(s.body, out)
            _h13_handlers(s.orelse, out)
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            _h13_handlers(s.body, out)
            _h13_handlers(s.orelse, out)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            _h13_handlers(s.body, out)
        elif isinstance(s, ast.Match):
            for case in s.cases:
                _h13_handlers(case.body, out)


def _h13_escapes(stmts, loop_depth: int = 0) -> bool:
    """Does any raise/return — or a break that actually exits the
    flagged loop — sit on this handler's own paths? Nested defs are
    excluded (their control flow is the callee's), and ``loop_depth``
    tracks handler-internal loops so a `break` that only exits an
    inner for/while is NOT read as escaping the unbounded one."""
    for s in stmts:
        if isinstance(s, _H13_SCOPE_STOPS):
            continue
        if isinstance(s, (ast.Raise, ast.Return)):
            return True
        if isinstance(s, ast.Break) and loop_depth == 0:
            return True
        child_depth = loop_depth + 1 if isinstance(
            s, (ast.For, ast.AsyncFor, ast.While)) else loop_depth
        for child in ast.iter_child_nodes(s):
            if isinstance(child, _H13_SCOPE_STOPS):
                continue
            if _h13_escapes([child], child_depth):
                return True
    return False


class _H13RetryLoops(_ScopedVisitor):
    def visit_While(self, node: ast.While):
        if _h13_unbounded(node):
            handlers: List[ast.ExceptHandler] = []
            _h13_handlers(node.body, handlers)
            for handler in handlers:
                if not _h13_escapes(handler.body):
                    self.flag(
                        "H13", handler,
                        "retry-shaped `while True` on a serve/runtime"
                        "/data path: this except handler swallows and "
                        "loops again with no escape (raise/break/"
                        "return) — on sustained failure the loop "
                        "spins forever, unthrottled, amplifying load "
                        "on the failing dependency. Re-attempts must "
                        "be bounded and backed-off: run them under "
                        "resilience.RetryPolicy (attempts + "
                        "exponential backoff + retry budget, "
                        "docs/RESILIENCE.md), or suppress with "
                        "`# sparkdl-lint: allow[H13] -- <what bounds "
                        "and paces this loop>`")
        self.generic_visit(node)


def check_h13(tree: ast.AST, path: str) -> List[Finding]:
    if not _path_in(path, _H13_PATHS):
        return []
    v = _H13RetryLoops(path)
    v.visit(tree)
    return v.findings


# ---------------------------------------------------------------------------
# registry

RULES: Dict[str, Callable[[ast.AST, str], List[Finding]]] = {
    "H1": check_h1,
    "H2": check_h2,
    "H3": check_h3,
    "H4": check_h4,
    "H5": check_h5,
    "H6": check_h6,
    "H12": check_h12,
    "H13": check_h13,
}

_RULE_DOCS = {
    "H1": "implicit host transfers outside the allowlisted drain path "
          "(jax.device_get / .block_until_ready() / np.asarray over a "
          "jnp-producing call)",
    "H2": "jit/retrace hazards: trace-time side effects (time.*, "
          "print, stateful RNG, obs tracing spans) inside "
          "jit/pjit-compiled functions; mutable "
          "static_argnums/static_argnames literals",
    "H3": "concurrency discipline: lock-holding classes need "
          "__getstate__/__reduce__; writes to _lock_guards-declared "
          "fields must hold self._lock",
    "H4": "quiesce hygiene: bare except; silently swallowed "
          "exceptions in cleanup/finally paths",
    "H5": "clock discipline in sparkdl_tpu/obs/ and sparkdl_tpu/serve/"
          ": time.time()/datetime.now() banned — span/latency math "
          "shares the tracer's time.perf_counter clock",
    "H6": "metric-name cardinality: registry counter/gauge/reservoir "
          "names interpolating a request id (request_id/req_id/rid) "
          "banned — per-request values go to the RequestLog / "
          "exemplars / span args, never into metric names",
    "H7": "lock-order cycles (whole-program): the acquired-while-"
          "holding graph across every analyzed module must be acyclic "
          "— any cycle is a deadlock schedule, reported with its "
          "module-by-module witness path (the PR-2 collective-enqueue "
          "shape)",
    "H8": "blocking call under a lock (whole-program): device syncs, "
          "Condition/Event waits, queue.get, time.sleep, file/socket "
          "I/O, thread joins — direct or through any resolved call "
          "chain — while a lock is held",
    "H9": "contract drift: registry keys / span lanes / env vars / "
          "/statusz fields the code publishes vs the docs tables "
          "(docs/OBSERVABILITY.md, docs/SERVING.md, "
          "docs/PERFORMANCE.md), BOTH directions — undocumented "
          "publishes and documented-but-gone names both fail",
    "H10": "effectful call reachable from jit (whole-program): any "
           "effect — registry writes, spans, logging, clocks/RNG, "
           "transfers, I/O, lock acquires, mutation of captured "
           "state — transitively reachable from a jax.jit/pjit-traced "
           "body through resolved call edges, with the witness chain "
           "printed; plus mutable state (lists/dicts/instance attrs) "
           "captured into a jitted function — the stale-value/"
           "retrace hazard the lexical H2 cannot see",
    "H11": "resource lifecycle (whole-program): an object whose class "
           "defines close/quiesce/shutdown/disarm — plus open()/"
           "tempfile handles and obs-singleton arm()s — constructed "
           "in a scope must reach its terminator there or escape "
           "(returned, stored on self/a global, registered, passed "
           "on); a leaked lifecycle keeps threads/sockets/arm state "
           "alive past the scope",
    "H12": "exception-flow accounting (sparkdl_tpu/serve/, obs/, "
           "runtime/): an except that swallows — pass, bare "
           "continue, or log-only — must record a failure counter/"
           "SLO outcome on the handler path or carry an inline "
           "suppression (the PR-7 population-separation fix as a "
           "static invariant)",
    "H13": "unbounded retry loops (sparkdl_tpu/serve/, runtime/, "
           "data/, resilience/): a `while True` whose except handler "
           "swallows and loops again with no escape — re-attempts "
           "must be bounded and backed-off (resilience.RetryPolicy: "
           "attempts + exponential backoff + retry budget), never a "
           "bare spin on a failing dependency",
    "H14": "hot-path host sync (whole-program): a device-resident "
           "value materialized on host — np.asarray/np.array, "
           ".item()/.tolist(), float()/int()/bool()/len(), "
           "truthiness, iteration — inside a function transitively "
           "reachable from the runner dispatch/drain loops, the "
           "serve dispatcher, the engine stream/re-chunk path, or "
           "the estimator step loops (the watchdog-beating roots), "
           "anywhere except the sanctioned timed_device_get drain; "
           "the hot witness chain is printed module-by-module",
    "H15": "missing buffer donation (whole-program): a call of a "
           "jax.jit/ModelFunction.jitted()-compiled callable whose "
           "device-array argument is dead after the call (last "
           "lexical use, no escape, not loop-carried) but the "
           "compile site declares no donate_argnums — XLA keeps the "
           "input buffer alive instead of reusing its HBM for the "
           "outputs (the parallel/train.py donate_argnums=(0,) "
           "precedent)",
    "H16": "dtype widening on a hot path (whole-program): Python "
           "float / np.float64 scalars and dtype-less "
           "np.zeros/ones/arange/asarray mixed into arithmetic with "
           "a device-tracked value on a hot function — the promoted "
           "float64 payload is a silent 2x byte tax on a link-bound "
           "pipeline; pin the dtype at the producer",
    "H17": "unguarded access to a guarded attribute (whole-program): "
           "a read/write of a class attribute the guarded-by "
           "inference ties to a lock (majority of accesses hold it, "
           "or `_lock_guards` declares it), from a function >= 2 "
           "threads may execute (thread-topology reachability over "
           "the call graph), without the guard held — the witness "
           "names both thread roots, the lock, and the vote",
    "H18": "unsafe publication (whole-program): a mutable local "
           "handed across a thread boundary — Thread/Timer args, "
           "executor submit/map, a done-callback, or closure capture "
           "by the spawned def — then mutated on both sides with no "
           "common lock; hand over a snapshot or share a lock",
    "H19": "atomicity split (whole-program): check-then-act on a "
           "guarded attribute where the check's lock hold ends "
           "before the acting hold — both sides locked, decision "
           "stale (the TOCTOU on self._closed / queue-depth "
           "patterns); widen one hold over both",
}


def rule_doc(rule: str) -> str:
    return _RULE_DOCS[rule.upper()]
