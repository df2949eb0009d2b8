"""Device-dataflow tracking + the H14–H16 throughput-hazard rules.

ROADMAP's own verdict on rounds 6–10 is "safety and visibility, not
speed": the pipeline is still link/host-bound while the analyzer
polices only correctness. This module points the same whole-program
machinery (the PR-8 call graph, the PR-9 effect facts' scan shape) at
the *throughput* bugs that pipeline work keeps reintroducing —
implicit host syncs on hot loops, undonated dead device buffers,
silent dtype widening on a link that is already the wall.

Per function, one scan records a serializable, replayable **event
stream** (``DeviceFlow``): device-value seeds (``jnp.*`` producers,
``jax.device_put``, results of jitted callables), propagation
(assignments, tuple unpacks, calls whose resolved callee returns a
device value), jit-callable bindings (``jax.jit(f)`` /
``ModelFunction.jitted()`` — with or without ``donate_argnums``),
materialization candidates, widening candidates, and the
liveness/escape facts donation analysis needs. The stream rides the
per-file result cache inside ``ModuleFacts`` exactly like the lock
and effect facts (ANALYZER_VERSION bumps force the cold re-analysis
the cache tests pin).

At program time the stream is **replayed** against the resolved call
graph (memoized, cycle-guarded — the same discipline as ``may_block``
/ ``may_effect``), which is what lets device-ness cross function
boundaries: ``gx, gy = place(xb, yb)`` tracks because ``place``'s own
replay proves its return is device-resident, and ``jitted, _, _ =
est._compile_step(step, bs)`` binds a jit callable because
``_compile_step``'s replay proves tuple index 0 is a ``jax.jit``
result (and whether it donates).

Three rules consume the facts, gated by
:class:`~sparkdl_tpu.analysis.hotpath.HotPaths` where noted:

* **H14 — hot-path host sync**: a device→host materialization of a
  tracked value on a HOT function — ``np.asarray``/``np.array`` over
  it, ``.item()``/``.tolist()``, ``float()``/``int()``/``bool()``/
  ``len()``, truthiness, iteration — anywhere except the sanctioned
  ``timed_device_get`` drain (allowlisted). Each finding prints the
  hot witness chain module-by-module. Explicit ``jax.device_get`` /
  ``.block_until_ready()`` stay H1's per-file beat (flagged
  everywhere, hot or cold) — one decision must never need two
  suppressions, the H10-vs-H2 division contract.
* **H15 — missing buffer donation**: a call of a jit-compiled
  callable whose device-tracked positional argument is DEAD after
  the call (locally assigned, last lexical load is the call, never
  escapes, not loop-carried from outside the call's loop) while the
  compile site carries no ``donate_argnums`` — the buffer's HBM
  could be reused for the outputs and instead a second copy is live
  across every step. Not hot-gated: a cold undonated step still
  wastes HBM at pod scale, where state is replicated N ways.
* **H16 — dtype widening**: a Python float literal, ``np.float64``
  scalar, or dtype-less ``np.zeros``/``ones``/``arange``/``asarray``
  mixed into arithmetic with a device-tracked value on a HOT
  function — under x64 (and on the host staging side uniformly)
  that promotes the payload to float64, a silent 2× byte tax on a
  link-bound pipeline. Pin the dtype at the producer.

Deliberate blind spots (documented in docs/LINT.md's limitations
section): resolution is lexical — values flowing through containers,
``**kwargs``, attributes, or unresolved callees are untracked (a
missed sync costs recall the fixtures pin; a guessed edge would
manufacture false findings), and deadness is per-function (an
argument whose caller retains a reference is excluded by the
params-are-never-dead rule, not by interprocedural escape analysis).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from sparkdl_tpu.analysis.findings import Finding
from sparkdl_tpu.analysis.hotpath import (
    WATCHDOG_MARKERS,
    HotPaths,
    _resolve as _hot_resolve,
)
from sparkdl_tpu.analysis.locks import CallEvent

#: replay recursion bound (same rationale as callgraph.MAX_DEPTH)
MAX_DEPTH = 8

# ---------------------------------------------------------------------------
# classification tables

# ONE copy of the dotted-name walk and the jit/partial name tables:
# the H2/H10/H15 rules must agree on what "a jit" is (one decision,
# one suppression), so the tables live in effects.py and are shared —
# a new jit alias added there covers every consumer at once.
from sparkdl_tpu.analysis.effects import (  # noqa: E402
    _JIT_NAMES,
    _PARTIAL_NAMES,
    _dotted,
)

#: dotted-call prefixes/names whose RESULT lives on device
_PRODUCER_PREFIXES = ("jnp.", "jax.numpy.")
_PRODUCER_NAMES = {
    "jax.device_put", "jax.device_put_replicated",
    "jax.device_put_sharded", "jax.make_array_from_process_local_data",
}

_DONATE_KWARGS = {"donate_argnums", "donate_argnames"}

#: host materialization forms H14 owns (explicit jax.device_get /
#: .block_until_ready are H1's per-file beat — see module docstring)
_NP_WRAPS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
             "np.ascontiguousarray", "numpy.ascontiguousarray",
             "np.float64", "numpy.float64", "np.float32",
             "numpy.float32"}
_SCALAR_BUILTINS = {"float", "int", "bool", "len"}
_ITEM_ATTRS = {"item", "tolist"}

#: dtype-less numpy ctors that default to float64/int64 (H16)
_DTYPELESS_CTORS = {"np.zeros", "np.ones", "np.empty", "np.full",
                    "np.arange", "np.linspace", "np.asarray",
                    "np.array", "numpy.zeros", "numpy.ones",
                    "numpy.empty", "numpy.full", "numpy.arange",
                    "numpy.linspace", "numpy.asarray", "numpy.array"}
_F64_CTORS = {"np.float64", "numpy.float64"}


def _is_producer(call: ast.Call) -> Optional[str]:
    name = _dotted(call.func)
    if name is None:
        return None
    if name in _PRODUCER_NAMES or name.startswith(_PRODUCER_PREFIXES):
        return name
    return None


def _jit_value(call: ast.Call) -> Optional[bool]:
    """``donated`` when ``call`` *produces* a jit-compiled callable:
    ``jax.jit(f, ...)``, ``partial(jax.jit, ...)``, or the repo's
    ``<model_fn>.jitted(...)`` form. None when it is not one."""
    name = _dotted(call.func)
    if name in _JIT_NAMES or (
            name in _PARTIAL_NAMES and call.args
            and _dotted(call.args[0]) in _JIT_NAMES):
        donated = any(kw.arg in _DONATE_KWARGS and not (
            isinstance(kw.value, ast.Constant)
            and kw.value.value in (False, None))
            for kw in call.keywords)
        return donated
    if isinstance(call.func, ast.Attribute) and \
            call.func.attr == "jitted":
        donated = any(kw.arg in _DONATE_KWARGS and not (
            isinstance(kw.value, ast.Constant)
            and kw.value.value in (False, None))
            for kw in call.keywords)
        if not donated and call.args:
            donated = not (isinstance(call.args[0], ast.Constant)
                           and call.args[0].value in (False, None))
        return donated
    return None


def _jit_decorated(fn: ast.AST) -> Optional[bool]:
    """``donated`` when ``fn`` carries a jit decorator, else None."""
    for dec in getattr(fn, "decorator_list", ()):
        if _dotted(dec) in _JIT_NAMES:
            return False
        if isinstance(dec, ast.Call):
            d = _jit_value(dec)
            if d is not None:
                return d
    return None


def _widen_source(node: ast.AST) -> Optional[str]:
    """A human description when ``node`` is an H16 widening operand."""
    if isinstance(node, ast.Constant) and isinstance(node.value, float):
        return f"Python float literal `{node.value}`"
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name in _F64_CTORS:
            return f"`{name}(...)` float64 scalar"
        if name in _DTYPELESS_CTORS:
            has_dtype = any(kw.arg == "dtype" for kw in node.keywords)
            last = name.rsplit(".", 1)[-1]
            if last in ("asarray", "array"):
                has_dtype = has_dtype or len(node.args) >= 2
            elif last == "full":
                # np.full(shape, fill_value[, dtype]) — dtype is the
                # THIRD positional; two args is the dtype-less form
                has_dtype = has_dtype or len(node.args) >= 3
            if not has_dtype:
                return f"dtype-less `{name}(...)` (defaults float64/" \
                       "int64)"
    return None


def _param_names(fn: ast.AST) -> List[str]:
    """POSITIONAL-ORDERED parameter names (posonly, then regular),
    with keyword-only/vararg/kwarg appended — order matters: the
    arg→param device-ness propagation maps call-site positions onto
    the callee's positional slots."""
    args = getattr(fn, "args", None)
    if args is None:
        return []
    names = [a.arg for a in args.posonlyargs + args.args]
    names.extend(a.arg for a in args.kwonlyargs)
    for special in (args.vararg, args.kwarg):
        if special is not None:
            names.append(special.arg)
    return names


# ---------------------------------------------------------------------------
# the serializable per-function facts


def _loops_of(ctx: Tuple[int, ...]) -> Tuple[int, ...]:
    """The loop components of an event context: positive ids are
    loops, negative ids are conditional branches (if/except/match
    arms) — see :class:`FlowScanner`."""
    return tuple(i for i in ctx if i > 0)


@dataclass
class FlowEvent:
    """One replayable event. ``data`` is a JSON-able dict whose shape
    depends on ``kind``:

    * ``assign`` — ``targets`` (names), ``value`` (descriptor:
      ``{"v": "producer"|"name"|"jit"|"call"|"other", ...}``)
    * ``call`` — ``ckind``/``cname``/``qual``/``display`` (the
      CallEvent shape) + ``args`` (positional bare-Name args) +
      optional ``jit``/``donated`` for direct ``jax.jit(f)(x)`` calls
    * ``sync`` — ``form``, ``name``, ``what``
    * ``widen`` — ``name``, ``other``
    * ``defjit`` — ``name``, ``donated`` (a jit-decorated nested def)
    * ``return`` — ``elts``: list of value descriptors
    * ``escape`` — ``name``, ``how``
    """

    kind: str
    line: int
    #: enclosing control context, outermost first: positive ids are
    #: loops, negative ids conditional branches (if/except/match
    #: arms). H15's deadness check needs both: an argument's latest
    #: assignment must sit in the SAME loop chain as the call (else
    #: it is loop-carried) and on a path that DOMINATES the call
    #: (else iterations skipping the assigning branch reuse the
    #: previous iteration's buffer across the back-edge).
    loops: Tuple[int, ...]
    data: dict


@dataclass
class DeviceFlow:
    """The per-function device-dataflow summary (serializable)."""

    key: str
    hot_root: bool = False
    root_label: str = ""
    #: POSITIONAL-ordered parameter names (the arg→param propagation
    #: maps call-site positions onto these slots)
    params: List[str] = field(default_factory=list)
    #: name -> last source line holding a Load of it (this scope only)
    last_load: Dict[str, int] = field(default_factory=dict)
    #: name -> EVERY source line holding a Load of it — deadness needs
    #: the full set: a read lexically ABOVE the reaching assignment
    #: but inside the call's loop is a back-edge read of the previous
    #: iteration's buffer, so donating it would be use-after-donate
    loads: Dict[str, List[int]] = field(default_factory=dict)
    #: loop id -> (first, last) source line of the loop statement
    loop_spans: Dict[int, Tuple[int, int]] = field(default_factory=dict)
    events: List[FlowEvent] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {"key": self.key, "hot_root": self.hot_root,
                "root_label": self.root_label, "params": self.params,
                "last_load": self.last_load,
                "loads": self.loads,
                "loop_spans": {str(k): list(v)
                               for k, v in self.loop_spans.items()},
                "events": [[e.kind, e.line, list(e.loops), e.data]
                           for e in self.events]}

    @classmethod
    def from_dict(cls, d: dict) -> "DeviceFlow":
        df = cls(key=d["key"], hot_root=d["hot_root"],
                 root_label=d.get("root_label", ""),
                 params=list(d["params"]),
                 last_load={k: int(v)
                            for k, v in d["last_load"].items()},
                 loads={k: [int(v) for v in vs]
                        for k, vs in d["loads"].items()},
                 loop_spans={int(k): (v[0], v[1])
                             for k, v in d["loop_spans"].items()})
        df.events = [FlowEvent(e[0], e[1], tuple(e[2]), e[3])
                     for e in d["events"]]
        return df


# ---------------------------------------------------------------------------
# the per-function scan


class FlowScanner:
    """One function body → its ordered :class:`DeviceFlow` event
    stream. Nested defs are NOT descended into (they are scanned as
    their own functions) — but their jit decoration is recorded
    (``defjit``) and the local names they capture become escapes."""

    def __init__(self, key: str, imports: Dict[str, str],
                 cls: Optional[str] = None):
        self.flow = DeviceFlow(key=key)
        self.imports = imports
        self.cls = cls
        self._loops: Tuple[int, ...] = ()
        self._loop_counter = 0
        self._branch_counter = 0

    # -- helpers -------------------------------------------------------------

    def _emit(self, kind: str, line: int, data: dict) -> None:
        self.flow.events.append(FlowEvent(kind, line, self._loops,
                                          data))

    def _load(self, name: str, line: int) -> None:
        prev = self.flow.last_load.get(name, 0)
        if line > prev:
            self.flow.last_load[name] = line
        self.flow.loads.setdefault(name, []).append(line)

    @staticmethod
    def _root_name(node: ast.AST) -> Optional[str]:
        """The base Name of ``x`` / ``x[...]`` / ``x.attr`` chains —
        what the tracked set is keyed by."""
        while isinstance(node, (ast.Subscript, ast.Attribute)):
            node = node.value
        return node.id if isinstance(node, ast.Name) else None

    def _import_source(self, dotted: str) -> str:
        head, _, rest = dotted.partition(".")
        src = self.imports.get(head)
        if src is None:
            return dotted
        return f"{src}.{rest}" if rest else src

    def _value_descriptor(self, node: ast.AST) -> dict:
        """Classify an assigned/returned expression."""
        if isinstance(node, ast.Name):
            return {"v": "name", "name": node.id}
        if isinstance(node, (ast.BinOp, ast.UnaryOp)):
            # arithmetic PROPAGATES device-ness: `y = dev * dev` is a
            # device array, and the per-step `y.item()` downstream is
            # exactly the sync H14 exists to catch
            names = sorted({n.id for n in ast.walk(node)
                            if isinstance(n, ast.Name)})
            if names:
                return {"v": "binop", "names": names}
            return {"v": "other"}
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                             ast.GeneratorExp)):
            elt = node.value if isinstance(node, ast.DictComp) \
                else node.elt
            if isinstance(elt, ast.Call) and _is_producer(elt):
                # a host CONTAINER of device arrays: len()/iteration
                # over it are free host-list ops (no H14), but handing
                # it to a jit call is a pytree whose buffers donation
                # analysis (H15) still covers
                return {"v": "producer", "container": True,
                        "what": _is_producer(elt) or ""}
            return {"v": "other"}
        if isinstance(node, ast.Call):
            donated = _jit_value(node)
            if donated is not None:
                return {"v": "jit", "donated": donated,
                        "what": _dotted(node.func) or "jax.jit"}
            producer = _is_producer(node)
            if producer is not None:
                return {"v": "producer", "what": producer}
            call = self._call_shape(node)
            if call is not None:
                return {"v": "call", **call}
        return {"v": "other"}

    def _call_shape(self, node: ast.Call) -> Optional[dict]:
        """The resolvable CallEvent shape of a call, or None — the
        SAME qualifier contract as locks.FunctionScanner._record_call:
        ``self`` calls carry the enclosing class, dotted calls the
        IMPORT SOURCE (not the local alias), so CallGraph.resolve sees
        identical events from both layers."""
        name = _dotted(node.func)
        if name is None:
            return None
        parts = name.split(".")
        # positional slots, None where the arg is not a bare name —
        # position is what the arg→param propagation and the H15
        # donate index key on
        args = [a.id if isinstance(a, ast.Name) else None
                for a in node.args]
        if parts[0] == "self" and len(parts) == 2:
            return {"ckind": "self", "cname": parts[1],
                    "qual": self.cls or "",
                    "display": name, "args": args}
        if len(parts) == 1:
            return {"ckind": "name", "cname": parts[0], "qual": "",
                    "display": name, "args": args}
        if len(parts) == 2 and parts[0] in self.imports:
            return {"ckind": "dotted", "cname": parts[1],
                    "qual": self.imports[parts[0]],
                    "display": name, "args": args}
        return {"ckind": "method", "cname": parts[-1], "qual": "",
                "display": name, "args": args}

    # -- entry ---------------------------------------------------------------

    def scan(self, fn: ast.AST) -> DeviceFlow:
        self.flow.params = _param_names(fn)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        self._walk(body)
        return self.flow

    # -- statements ----------------------------------------------------------

    def _walk(self, stmts) -> None:
        for stmt in stmts:
            self._visit(stmt)

    def _visit(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            donated = _jit_decorated(stmt)
            if donated is not None:
                self._emit("defjit", stmt.lineno,
                           {"name": stmt.name, "donated": donated})
            self._escape_captures(stmt, "captured by nested def")
            return
        if isinstance(stmt, ast.ClassDef):
            self._escape_captures(stmt, "captured by nested class")
            return
        if isinstance(stmt, (ast.Global, ast.Nonlocal)):
            for name in stmt.names:
                self._emit("escape", stmt.lineno,
                           {"name": name, "how": "global/nonlocal "
                                                 "state"})
            return
        if isinstance(stmt, ast.Assign):
            self._scan_expr(stmt.value)
            targets: List[str] = []
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Name):
                    targets.append(tgt.id)
                elif isinstance(tgt, (ast.Tuple, ast.List)):
                    targets.extend(e.id for e in tgt.elts
                                   if isinstance(e, ast.Name))
                elif isinstance(tgt, (ast.Attribute, ast.Subscript)):
                    # ownership moved to longer-lived state
                    self._scan_expr(tgt)
                    for node in ast.walk(stmt.value):
                        if isinstance(node, ast.Name):
                            self._emit("escape", stmt.lineno,
                                       {"name": node.id,
                                        "how": "stored on attr/"
                                               "container"})
            if targets:
                self._emit("assign", stmt.lineno,
                           {"targets": targets,
                            "value":
                                self._value_descriptor(stmt.value)})
            return
        if isinstance(stmt, ast.AugAssign):
            self._scan_expr(stmt.value)
            if isinstance(stmt.target, ast.Name):
                other = _widen_source(stmt.value)
                if other is not None:
                    self._emit("widen", stmt.lineno,
                               {"name": stmt.target.id,
                                "other": other})
                self._load(stmt.target.id, stmt.lineno)
            return
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._scan_expr(stmt.value)
                if isinstance(stmt.target, ast.Name):
                    self._emit("assign", stmt.lineno,
                               {"targets": [stmt.target.id],
                                "value":
                                    self._value_descriptor(stmt.value)})
            return
        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self._scan_expr(stmt.value)
                elts = (stmt.value.elts
                        if isinstance(stmt.value, ast.Tuple)
                        else [stmt.value])
                self._emit("return", stmt.lineno,
                           {"elts": [self._value_descriptor(e)
                                     for e in elts]})
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            if isinstance(stmt.iter, ast.Name):
                self._emit("sync", stmt.lineno,
                           {"form": "iteration", "name": stmt.iter.id,
                            "what": f"`for ... in {stmt.iter.id}:`"})
            self._scan_expr(stmt.iter)
            self._in_loop(stmt.body, stmt)
            self._in_branch(stmt.orelse)
            return
        if isinstance(stmt, ast.While):
            self._truth_test(stmt.test)
            self._scan_expr(stmt.test)
            self._in_loop(stmt.body, stmt)
            self._in_branch(stmt.orelse)
            return
        if isinstance(stmt, ast.If):
            self._truth_test(stmt.test)
            self._scan_expr(stmt.test)
            self._in_branch(stmt.body)
            self._in_branch(stmt.orelse)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._scan_expr(item.context_expr)
            self._walk(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            self._in_branch(stmt.body)
            for handler in stmt.handlers:
                self._in_branch(handler.body)
            self._in_branch(stmt.orelse)
            # finalbody is unconditional — no branch context
            self._walk(stmt.finalbody)
            return
        if isinstance(stmt, ast.Match):
            self._scan_expr(stmt.subject)
            for case in stmt.cases:
                self._in_branch(case.body)
            return
        if isinstance(stmt, ast.Expr):
            self._scan_expr(stmt.value)
            return
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.stmt):
                self._visit(child)
            elif isinstance(child, ast.expr):
                self._scan_expr(child)

    def _in_loop(self, body, stmt: ast.stmt) -> None:
        self._loop_counter += 1
        self.flow.loop_spans[self._loop_counter] = (
            stmt.lineno,
            getattr(stmt, "end_lineno", stmt.lineno) or stmt.lineno)
        outer = self._loops
        self._loops = outer + (self._loop_counter,)
        self._walk(body)
        self._loops = outer

    def _in_branch(self, body) -> None:
        """A conditionally-executed arm (if/except/match/loop-else):
        negative context id, so deadness analysis can tell a
        dominating assignment from a maybe-skipped one."""
        if not body:
            return
        self._branch_counter += 1
        outer = self._loops
        self._loops = outer + (-self._branch_counter,)
        self._walk(body)
        self._loops = outer

    def _truth_test(self, test: ast.AST) -> None:
        node = test
        if isinstance(node, ast.UnaryOp) and isinstance(node.op,
                                                        ast.Not):
            node = node.operand
        if isinstance(node, ast.Name):
            self._emit("sync", node.lineno,
                       {"form": "truthiness", "name": node.id,
                        "what": f"`if {node.id}:` truth test"})

    def _escape_captures(self, fn: ast.AST, how: str) -> None:
        """FREE names a nested def/class/lambda body loads become
        escapes: the capture keeps the value alive in a scope this
        per-function pass cannot see. Names the nested scope binds
        itself (params, assignment/loop targets) are its own locals,
        not captures — EXCEPT names it declares ``nonlocal``/
        ``global``: a Store to those rebinds the OUTER binding, so
        both their loads and stores are captures."""
        declared: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                declared.update(node.names)
        bound: Set[str] = set(_param_names(fn)) - declared
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(
                    node.ctx, (ast.Store, ast.Del)) \
                    and node.id not in declared:
                bound.add(node.id)
        seen: Set[str] = set()
        for name in sorted(declared):
            seen.add(name)
            self._emit("escape", getattr(fn, "lineno", 1),
                       {"name": name, "how": how + " (nonlocal)"})
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load) and node.id not in bound \
                    and node.id not in seen:
                seen.add(node.id)
                self._emit("escape", getattr(fn, "lineno", 1),
                           {"name": node.id, "how": how})

    # -- expressions ---------------------------------------------------------

    def _scan_expr(self, expr: ast.AST) -> None:
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Lambda):
                self._escape_captures(node, "captured by lambda")
                continue
            if isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load):
                self._load(node.id, node.lineno)
            elif isinstance(node, ast.Call):
                self._scan_call(node)
            elif isinstance(node, ast.BinOp):
                self._scan_binop(node)
            elif isinstance(node, (ast.Yield, ast.YieldFrom)) \
                    and node.value is not None:
                for n in ast.walk(node.value):
                    if isinstance(n, ast.Name):
                        self._emit("escape", node.lineno,
                                   {"name": n.id, "how": "yielded"})
            stack.extend(ast.iter_child_nodes(node))

    def _scan_call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        # hot-root markers: a call whose import source is the watchdog
        # watch/pulse marks this function as a hot-loop root
        if name is not None:
            src = self._import_source(name)
            if any(src.endswith(m) for m in WATCHDOG_MARKERS):
                self.flow.hot_root = True
        # direct invocation of a jit expression: jax.jit(f)(x) /
        # model_fn.jitted()(x)
        if isinstance(node.func, ast.Call):
            donated = _jit_value(node.func)
            if donated is not None:
                self._emit("call", node.lineno, {
                    "ckind": "direct-jit", "cname": "<jit>",
                    "qual": "",
                    "display": _dotted(node.func.func) or "jax.jit",
                    "args": [a.id if isinstance(a, ast.Name) else None
                             for a in node.args],
                    "end": getattr(node, "end_lineno", node.lineno)
                    or node.lineno,
                    "jit": True, "donated": donated})
                return
        if name is None:
            return
        if _jit_value(node) is not None or (
                name in _PARTIAL_NAMES and node.args
                and _dotted(node.args[0]) in _JIT_NAMES):
            return      # a compile, not a call — handled as a value
        # H14 materialization candidates
        attr = node.func.attr if isinstance(node.func, ast.Attribute) \
            else None
        if name in _NP_WRAPS and node.args:
            root = self._root_name(node.args[0])
            if root is not None:
                self._emit("sync", node.lineno,
                           {"form": "np-wrap", "name": root,
                            "what": f"`{name}(...)`"})
                return
        if name in _SCALAR_BUILTINS and len(node.args) == 1:
            root = self._root_name(node.args[0])
            if root is not None:
                self._emit("sync", node.lineno,
                           {"form": name, "name": root,
                            "what": f"`{name}(...)`"})
            return      # a scalar builtin retains nothing: not a call
        if attr in _ITEM_ATTRS and not node.args:
            root = self._root_name(node.func.value)
            if root is not None:
                self._emit("sync", node.lineno,
                           {"form": attr, "name": root,
                            "what": f"`.{attr}()`"})
                return
        call = self._call_shape(node)
        if call is not None:
            call["end"] = getattr(node, "end_lineno",
                                  node.lineno) or node.lineno
            self._emit("call", node.lineno, call)

    def _scan_binop(self, node: ast.BinOp) -> None:
        for side, other in ((node.left, node.right),
                            (node.right, node.left)):
            if not isinstance(side, ast.Name):
                continue
            desc = _widen_source(other)
            if desc is not None:
                self._emit("widen", node.lineno,
                           {"name": side.id, "other": desc})


def scan_flow(fn: ast.AST, key: str, imports: Dict[str, str],
              cls: Optional[str] = None) -> DeviceFlow:
    """One function body → its :class:`DeviceFlow` facts. ``cls`` is
    the enclosing class (``self.m()`` resolution needs it)."""
    return FlowScanner(key, imports, cls).scan(fn)


# ---------------------------------------------------------------------------
# program-time replay


@dataclass
class _SyncHit:
    line: int
    form: str
    name: str
    what: str


@dataclass
class _WidenHit:
    line: int
    name: str
    other: str


@dataclass
class _DonateHit:
    line: int
    callee: str              # display name of the jit callable
    arg: str
    index: int
    compile_note: str        # where/how it was compiled


@dataclass
class _Result:
    """One function's replay outcome."""

    ret_device: bool = False
    #: the returned device value is a host CONTAINER of device arrays
    #: (a comprehension result): H15-relevant, H14-exempt
    ret_container: bool = False
    #: tuple index -> donated for returned jit callables
    ret_jit: Dict[int, bool] = field(default_factory=dict)
    syncs: List[_SyncHit] = field(default_factory=list)
    widens: List[_WidenHit] = field(default_factory=list)
    donates: List[_DonateHit] = field(default_factory=list)


_EMPTY = _Result()


def _flows_index(graph) -> Dict[str, DeviceFlow]:
    idx: Dict[str, DeviceFlow] = {}
    for m in graph.modules.values():
        idx.update(getattr(m, "flows", {}))
    return idx


class _FlowState:
    """Cached per-CallGraph analysis state shared by H14/H15/H16.

    Replays run in bounded ROUNDS: each round re-replays every
    function with the previous round's arg→param device seeds (a
    caller passing a tracked value into a resolved callee makes the
    callee's positional parameter device-tracked), so device-ness
    crosses call edges as arguments as well as returns. Three rounds
    cover every real chain (depth-2 argument hand-offs); the loop
    stops early once the seed set stops growing."""

    _ROUNDS = 3

    def __init__(self, graph):
        self.graph = graph
        self.idx = _flows_index(graph)
        self.hot = HotPaths(graph, self.idx)
        self.memo: Dict[str, _Result] = {}
        self.param_seeds: Dict[str, Set[str]] = {}
        self._next_seeds: Dict[str, Set[str]] = {}
        for round_no in range(self._ROUNDS):
            self.memo = {}
            self._next_seeds = {}
            for key in self.idx:
                self.result(key)
            grew = any(n - self.param_seeds.get(k, set())
                       for k, n in self._next_seeds.items())
            if not grew or round_no == self._ROUNDS - 1:
                # converged — or the bounded-depth cutoff: growth on
                # the final round is dropped by design (a deeper
                # argument chain waits for the bound, exactly like
                # MAX_DEPTH), never merged into seeds the memoized
                # results were not computed with
                break
            for k, n in self._next_seeds.items():
                self.param_seeds.setdefault(k, set()).update(n)

    def result(self, key: str, _stack: Optional[Set[str]] = None,
               depth: int = MAX_DEPTH) -> _Result:
        if key in self.memo:
            return self.memo[key]
        flow = self.idx.get(key)
        f = self.graph.functions.get(key)
        if flow is None or f is None or depth <= 0:
            return _EMPTY
        stack = _stack if _stack is not None else set()
        if key in stack:
            return _EMPTY
        stack.add(key)
        res = self._replay(flow, f, stack, depth)
        stack.discard(key)
        if _stack is None or depth == MAX_DEPTH:
            self.memo[key] = res
        return res

    # -- the replay ----------------------------------------------------------

    def _callee(self, f, data: dict) -> Optional[str]:
        if data.get("ckind") == "direct-jit":
            return None
        ev = CallEvent(kind=data["ckind"], name=data["cname"],
                       display=data.get("display", data["cname"]),
                       line=0, held=(), qualifier=data.get("qual", ""))
        return _hot_resolve(self.graph, f, ev)

    def _seed_params(self, target: str, data: dict,
                     tracked: Set[str]) -> None:
        """A tracked value handed positionally into a resolved callee
        seeds the matching parameter for the next replay round."""
        callee = self.idx.get(target)
        if callee is None:
            return
        params = callee.params
        offset = 1 if params and params[0] in ("self", "cls") \
            and data.get("ckind") in ("self", "method") else 0
        for i, arg in enumerate(data.get("args", [])):
            if arg is None or arg not in tracked:
                continue
            slot = i + offset
            if slot < len(params):
                self._next_seeds.setdefault(
                    target, set()).add(params[slot])

    def _replay(self, flow: DeviceFlow, f, stack: Set[str],
                depth: int) -> _Result:
        res = _Result()
        tracked: Set[str] = set(self.param_seeds.get(flow.key, ()))
        #: host containers of device arrays (H15-relevant, H14-exempt)
        containers: Set[str] = set()
        jitvars: Dict[str, Tuple[bool, str]] = {}   # name -> (donated, note)
        escapes: Set[str] = set()
        assigned: Dict[str, Tuple[int, Tuple[int, ...]]] = {}
        #: (line, end line, loops, callee display, args, donated,
        #: note, tracked-set snapshot, assigned-map snapshot) — both
        #: snapshots taken AT the call: a reassignment after the call
        #: must not change the verdict about the buffer fed INTO it
        jit_calls: List[Tuple[int, int, Tuple[int, ...], str,
                              List[str], bool, str, Set[str],
                              Dict[str, Tuple[int,
                                              Tuple[int, ...]]]]] = []

        def classify(value: dict
                     ) -> Tuple[Optional[str],
                                Optional[Tuple[bool, str]]]:
            """(device kind — None/"array"/"container", jit_info) for
            a value descriptor."""
            v = value.get("v")
            if v == "producer":
                return ("container" if value.get("container")
                        else "array"), None
            if v == "name":
                name = value["name"]
                kind = ("array" if name in tracked
                        else "container" if name in containers
                        else None)
                return kind, jitvars.get(name)
            if v == "binop":
                if any(n in tracked for n in value.get("names", ())):
                    return "array", None
                return None, None
            if v == "jit":
                return None, (bool(value.get("donated")),
                              f"`{value.get('what', 'jax.jit')}(...)`")
            if v == "call":
                callee = self._callee(f, value)
                if callee is None:
                    return None, None
                sub = self.result(callee, stack, depth - 1)
                jit0 = sub.ret_jit.get(0)
                info = None
                if jit0 is not None:
                    info = (jit0,
                            f"compiled inside "
                            f"`{value.get('display', '?')}(...)`")
                kind = ("array" if sub.ret_device
                        else "container" if sub.ret_container
                        else None)
                return kind, info
            return None, None

        for ev in flow.events:
            data = ev.data
            if ev.kind == "defjit":
                jitvars[data["name"]] = (
                    bool(data["donated"]),
                    f"`@jax.jit def {data['name']}` at line {ev.line}")
            elif ev.kind == "assign":
                targets = data["targets"]
                value = data["value"]
                for t in targets:
                    assigned[t] = (ev.line, ev.loops)
                v = value.get("v")
                if v == "call":
                    local_jit = jitvars.get(value["cname"]) \
                        if value.get("ckind") == "name" else None
                    if local_jit is not None:
                        # calling a locally-bound jit callable:
                        # results are device arrays
                        for t in targets:
                            tracked.add(t)
                            containers.discard(t)
                        continue
                    callee = self._callee(f, value)
                    if callee is not None:
                        sub = self.result(callee, stack, depth - 1)
                        for t in targets:
                            (tracked.add if sub.ret_device
                             else tracked.discard)(t)
                            (containers.add if sub.ret_container
                             else containers.discard)(t)
                        for idx, donated in sub.ret_jit.items():
                            if idx < len(targets):
                                jitvars[targets[idx]] = (
                                    donated,
                                    f"compiled inside "
                                    f"`{value.get('display', '?')}"
                                    "(...)`")
                        continue
                    for t in targets:
                        tracked.discard(t)
                        containers.discard(t)
                        jitvars.pop(t, None)
                    continue
                kind, jit_info = classify(value)
                for t in targets:
                    (tracked.add if kind == "array"
                     else tracked.discard)(t)
                    (containers.add if kind == "container"
                     else containers.discard)(t)
                    if jit_info is not None:
                        jitvars[t] = jit_info
                    else:
                        jitvars.pop(t, None)
            elif ev.kind == "call":
                args = data.get("args", [])
                end = int(data.get("end", ev.line))
                if data.get("ckind") == "direct-jit":
                    jit_calls.append((ev.line, end, ev.loops,
                                      data.get("display", "<jit>"),
                                      args, bool(data.get("donated")),
                                      "compiled at the call site",
                                      tracked | containers,
                                      dict(assigned)))
                    continue
                local_jit = jitvars.get(data["cname"]) \
                    if data.get("ckind") == "name" else None
                if local_jit is not None:
                    donated, note = local_jit
                    jit_calls.append((ev.line, end, ev.loops,
                                      data["cname"], args, donated,
                                      note, tracked | containers,
                                      dict(assigned)))
                    continue
                # an argument handed to any other call may be retained
                # by the callee — alive for donation purposes; a
                # TRACKED argument into a resolved callee also seeds
                # that callee's parameter as device-resident for the
                # next propagation round
                target = self._callee(f, data)
                if target is not None:
                    self._seed_params(target, data, tracked)
                for a in args:
                    if a is not None:
                        escapes.add(a)
            elif ev.kind == "sync":
                if data["name"] in tracked:
                    res.syncs.append(_SyncHit(ev.line, data["form"],
                                              data["name"],
                                              data["what"]))
            elif ev.kind == "widen":
                if data["name"] in tracked:
                    res.widens.append(_WidenHit(ev.line, data["name"],
                                                data["other"]))
            elif ev.kind == "escape":
                escapes.add(data["name"])
            elif ev.kind == "return":
                for i, elt in enumerate(data["elts"]):
                    kind, jit_info = classify(elt)
                    if kind == "array":
                        res.ret_device = True
                    elif kind == "container":
                        res.ret_container = True
                    if jit_info is not None:
                        donated = jit_info[0]
                        # any undonated return path wins (conservative)
                        res.ret_jit[i] = (res.ret_jit.get(i, True)
                                          and donated)
                    if elt.get("v") == "name":
                        escapes.add(elt["name"])

        # H15: dead-after-call device args of undonated jit calls
        for line, end, loops, callee, args, donated, note, snap, \
                asn_at_call in jit_calls:
            if donated:
                continue
            for idx, arg in enumerate(args):
                if arg is None or arg not in snap:
                    continue            # not a (named) device value
                if arg in flow.params or arg in escapes:
                    continue            # lifetime extends past here
                info = asn_at_call.get(arg)
                if info is None:
                    continue            # never locally assigned
                if flow.last_load.get(arg, 0) > end:
                    continue            # read again later: alive
                a_line, a_ctx = info
                if _loops_of(a_ctx) != _loops_of(loops):
                    continue    # assigned in a different loop chain:
                    #             loop-carried, next iteration reads it
                if a_ctx != loops[:len(a_ctx)]:
                    continue    # assigned on a maybe-skipped branch
                    #             (if/except arm) the call does not sit
                    #             under: an iteration skipping the
                    #             branch would reuse the previous
                    #             buffer across the back-edge
                loop_ids = _loops_of(loops)
                if loop_ids:
                    # a read inside the call's loop but lexically
                    # ABOVE the reaching assignment runs on the NEXT
                    # iteration against this iteration's (donated)
                    # buffer — a back-edge read, alive
                    span = flow.loop_spans.get(loop_ids[-1])
                    if span is not None and any(
                            span[0] <= ln < a_line
                            for ln in flow.loads.get(arg, ())):
                        continue
                res.donates.append(_DonateHit(
                    line, callee, arg, idx, note))
        return res


def _flow_state(graph) -> _FlowState:
    state = getattr(graph, "_sparkdl_flow_state", None)
    if state is None or state.graph is not graph:
        state = _FlowState(graph)
        graph._sparkdl_flow_state = state
    return state


# ---------------------------------------------------------------------------
# the rules


#: per-form consequence clauses. Most forms BLOCK the calling thread
#: until the device catches up; len() is honest about being shape
#: metadata (it never blocks on jax arrays) — it is still flagged on
#: hot paths because per-batch length branching is the precursor of
#: the row-wise host iteration the rule exists to stop.
_BLOCKING_TAIL = ("— the calling thread blocks until the device "
                  "catches up, serializing the overlap the runner's "
                  "in-flight window exists to hide")
_SYNC_READING = {
    "np-wrap": f"copies the device buffer to host {_BLOCKING_TAIL}",
    "float": f"materializes the device scalar on host {_BLOCKING_TAIL}",
    "int": f"materializes the device scalar on host {_BLOCKING_TAIL}",
    "bool": f"materializes the device scalar on host {_BLOCKING_TAIL}",
    "len": ("probes the device shape in host control flow — len() "
            "itself reads static metadata (no device wait on jax "
            "arrays), but hot-loop code branching per batch on it is "
            "the precursor of row-wise host iteration; restructure "
            "to whole-batch ops"),
    "item": f"materializes the device scalar on host {_BLOCKING_TAIL}",
    "tolist": ("copies the device buffer to host, element-wise "
               f"{_BLOCKING_TAIL}"),
    "iteration": ("iterates the device array on host, row by row — "
                  "every element pays its own device→host round-trip "
                  "and the loop serializes behind the slowest one"),
    "truthiness": ("materializes the device value to branch on it "
                   f"{_BLOCKING_TAIL}"),
}


def check_h14(graph) -> List[Finding]:
    state = _flow_state(graph)
    findings: List[Finding] = []
    for key in sorted(state.idx):
        if not state.hot.is_hot(key):
            continue
        f = graph.functions.get(key)
        if f is None:
            continue
        res = state.result(key)
        for hit in res.syncs:
            findings.append(Finding(
                rule="H14", path=f.path, line=hit.line, col=0,
                qualname=f.qualname,
                message=(
                    f"{hit.what} over device-resident `{hit.name}` on "
                    f"a HOT path: "
                    f"{_SYNC_READING.get(hit.form, 'syncs on host')}; "
                    f"hot witness: {state.hot.why(key)}. Accumulate "
                    "device values and drain once per epoch/run "
                    "through the sanctioned timed_device_get path "
                    "instead (suppress: `# sparkdl-lint: allow[H14] "
                    "-- <why this sync must sit on the hot path>`)")))
    findings.sort(key=lambda x: (x.path, x.line))
    return findings


def check_h15(graph) -> List[Finding]:
    state = _flow_state(graph)
    findings: List[Finding] = []
    for key in sorted(state.idx):
        f = graph.functions.get(key)
        if f is None:
            continue
        res = state.result(key)
        for hit in res.donates:
            findings.append(Finding(
                rule="H15", path=f.path, line=hit.line, col=0,
                qualname=f.qualname,
                message=(
                    f"`{hit.callee}(...)` consumes device array "
                    f"`{hit.arg}` (positional {hit.index}) that is "
                    "DEAD after this call — last lexical use, no "
                    f"escape — but the jit ({hit.compile_note}) "
                    "declares no donate_argnums: XLA keeps the input "
                    "buffer alive across the call instead of reusing "
                    "its HBM for the outputs, double-buffering every "
                    "step (at pod scale, N replicas each pay it). "
                    f"Compile with `donate_argnums=({hit.index},)` "
                    "(the parallel/train.py precedent), or suppress "
                    "with `# sparkdl-lint: allow[H15] -- <who reads "
                    "the buffer after the call>`")))
    findings.sort(key=lambda x: (x.path, x.line))
    return findings


def check_h16(graph) -> List[Finding]:
    state = _flow_state(graph)
    findings: List[Finding] = []
    for key in sorted(state.idx):
        if not state.hot.is_hot(key):
            continue
        f = graph.functions.get(key)
        if f is None:
            continue
        res = state.result(key)
        for hit in res.widens:
            findings.append(Finding(
                rule="H16", path=f.path, line=hit.line, col=0,
                qualname=f.qualname,
                message=(
                    f"{hit.other} mixed into arithmetic with "
                    f"device-resident `{hit.name}` on a HOT path: "
                    "dtype-less numpy defaults are float64/int64, so "
                    "the promoted result doubles every payload byte "
                    "on a pipeline that is already link-bound "
                    "(BENCH_r05: pipeline_bound_by=link); hot "
                    f"witness: {state.hot.why(key)}. Pin the dtype at "
                    "the producer (np.float32 / the model dtype) or "
                    "suppress with `# sparkdl-lint: allow[H16] -- "
                    "<why the promotion is intended>`")))
    findings.sort(key=lambda x: (x.path, x.line))
    return findings
