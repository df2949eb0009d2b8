"""Package-wide symbol table + call graph for the H7/H8 program rules.

The per-file rules (H1–H6) see one module at a time; the concurrency
failure modes this repo has actually shipped fixes for — a serve-layer
lock held while a function from another module blocks inside it, two
modules acquiring the same pair of locks in opposite orders — only
exist in the WHOLE program. This module builds that view:

* :func:`scan_module` — one parsed module → :class:`ModuleFacts`:
  imports, class/method inventory, module/class lock identities
  (locks.py), and a per-function event stream (acquires, direct
  may-block operations, call sites — each carrying the lexically-held
  lock set at that point). The facts are plain-data serializable,
  which is what makes the analyzer's per-file result cache work.
* :class:`CallGraph` — all modules' facts → resolved call edges plus
  the two transitive facts the rules need, computed by bounded-depth
  memoized descent: ``may_block(f)`` (does any reachable callee block)
  and ``may_acquire(f)`` (which locks can a call into ``f`` end up
  taking), each with a recorded next-hop so a finding can print the
  actual witness chain module-by-module instead of "trust me".

Resolution is deliberately lexical (the sparkdl-lint contract): a
``self.m()`` call binds to the enclosing class's ``m``; a bare name to
the module table then the import table; ``mod.f`` through an imported
module; a plain ``obj.m()`` only when exactly ONE class in the
analyzed set defines ``m`` (the unique-method heuristic — ambiguity
resolves to "no edge", because a false edge would manufacture false
deadlocks, while a missed edge only costs recall the fixtures pin).
Bounded depth (:data:`MAX_DEPTH`) keeps the closure linear in
practice and is far deeper than any real chain in this repo.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from sparkdl_tpu.analysis import dataflow as _dataflow
from sparkdl_tpu.analysis import effects as _effects
from sparkdl_tpu.analysis import threads as _threads
from sparkdl_tpu.analysis.locks import (
    CallEvent,
    FunctionFacts,
    FunctionScanner,
    ModuleLocks,
    discover_locks,
)

#: transitive-closure depth bound: deep enough for every real chain
#: (serve dispatch -> runner.run -> dispatch_chunks -> sink.write ->
#: timed_device_get is 5), bounded so a pathological cycle costs
#: nothing
MAX_DEPTH = 8


def module_name(path: str) -> str:
    """A stable dotted module name from a (display) path: anchored at
    the package root when the path contains one, else the last two
    segments (``tools/fleet_pack.py`` → ``tools.fleet_pack``),
    else the stem."""
    norm = path.replace("\\", "/")
    parts = [p for p in norm.split("/") if p not in ("", ".")]
    stem = parts[-1][:-3] if parts[-1].endswith(".py") else parts[-1]
    parts = parts[:-1] + [stem]
    for anchor in ("sparkdl_tpu",):
        if anchor in parts:
            parts = parts[parts.index(anchor):]
            break
    else:
        parts = parts[-2:] if len(parts) >= 2 else parts
    if parts and parts[-1] == "__init__":
        parts = parts[:-1] or ["__init__"]
    return ".".join(parts)


@dataclass
class ModuleFacts:
    """Everything the program rules need from one module, plain data."""

    module: str
    path: str
    #: local name -> dotted source ("pkg.mod" for modules,
    #: "pkg.mod.obj" for from-imports)
    imports: Dict[str, str] = field(default_factory=dict)
    #: class name -> method names defined in its body
    classes: Dict[str, List[str]] = field(default_factory=dict)
    #: module-level function names
    functions: List[str] = field(default_factory=list)
    #: module-level lock names (confirms imported-lock candidates)
    module_locks: List[str] = field(default_factory=list)
    #: per-function facts, keyed "module::Qual"
    facts: Dict[str, FunctionFacts] = field(default_factory=dict)
    #: per-function effect facts (effects.py), same keys as ``facts``
    effects: Dict[str, "_effects.FunctionEffects"] = \
        field(default_factory=dict)
    #: per-function device-dataflow facts (dataflow.py), same keys
    flows: Dict[str, "_dataflow.DeviceFlow"] = \
        field(default_factory=dict)
    #: per-function thread/race facts (threads.py), same keys
    threads: Dict[str, "_threads.ThreadFacts"] = \
        field(default_factory=dict)
    #: class name -> attrs its ``_lock_guards`` declares (the H3
    #: convention, authoritative for guarded-by inference)
    class_guards: Dict[str, List[str]] = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"module": self.module, "path": self.path,
                "imports": self.imports, "classes": self.classes,
                "functions": self.functions,
                "module_locks": self.module_locks,
                "class_guards": self.class_guards,
                "facts": {k: f.to_dict() for k, f in self.facts.items()},
                "effects": {k: e.to_dict()
                            for k, e in self.effects.items()},
                "flows": {k: fl.to_dict()
                          for k, fl in self.flows.items()},
                "threads": {k: t.to_dict()
                            for k, t in self.threads.items()}}

    @classmethod
    def from_dict(cls, d: dict) -> "ModuleFacts":
        mf = cls(module=d["module"], path=d["path"],
                 imports=dict(d["imports"]),
                 classes={k: list(v) for k, v in d["classes"].items()},
                 functions=list(d["functions"]),
                 module_locks=list(d.get("module_locks", [])))
        mf.facts = {k: FunctionFacts.from_dict(v)
                    for k, v in d["facts"].items()}
        mf.effects = {k: _effects.FunctionEffects.from_dict(v)
                      for k, v in d.get("effects", {}).items()}
        mf.flows = {k: _dataflow.DeviceFlow.from_dict(v)
                    for k, v in d.get("flows", {}).items()}
        mf.threads = {k: _threads.ThreadFacts.from_dict(v)
                      for k, v in d.get("threads", {}).items()}
        mf.class_guards = {k: list(v) for k, v in
                           d.get("class_guards", {}).items()}
        return mf


def _class_guards(node: ast.ClassDef) -> List[str]:
    """The class-body ``_lock_guards = ("field", ...)`` declaration
    (the H3 convention — writes to these hold ``self._lock``), made
    visible to the program-level guarded-by inference (races.py)."""
    for item in node.body:
        if not isinstance(item, ast.Assign):
            continue
        for tgt in item.targets:
            if isinstance(tgt, ast.Name) and tgt.id == "_lock_guards" \
                    and isinstance(item.value, (ast.Tuple, ast.List)):
                return sorted({e.value for e in item.value.elts
                               if isinstance(e, ast.Constant)
                               and isinstance(e.value, str)})
    return []


def _collect_imports(tree: ast.Module) -> Dict[str, str]:
    imports: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                imports[alias.asname or alias.name.split(".")[0]] = \
                    alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                imports[alias.asname or alias.name] = \
                    f"{node.module}.{alias.name}"
    return imports


def scan_module(tree: ast.Module, path: str,
                module: Optional[str] = None) -> ModuleFacts:
    """One parsed module → its serializable program-analysis facts
    (call/lock facts for H7/H8 plus the effect/jit/capture/resource
    facts the H10/H11 effect system runs on)."""
    module = module or module_name(path)
    mf = ModuleFacts(module=module, path=path)
    mf.imports = _collect_imports(tree)
    locks: ModuleLocks = discover_locks(tree, module)
    #: class -> instance attrs bound to mutable containers (the
    #: capture analysis consults the ENCLOSING class of a jitted fn)
    cls_mutables: Dict[str, set] = {}
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            cls_mutables[node.name] = _effects.mutable_class_attrs(node)
    #: def name -> fact keys (resolves `jax.jit(step)` call forms)
    name_keys: Dict[str, List[str]] = {}

    def scan_fn(fn, qualname: str, cls: Optional[str],
                enclosing_mutables: Dict[str, int]):
        scanner = FunctionScanner(module, path, cls, qualname, locks,
                                  mf.imports)
        scanner.scan(fn)
        key = f"{module}::{qualname}"
        mf.facts[key] = FunctionFacts(
            key=key, module=module, path=path, qualname=qualname,
            line=fn.lineno, acquires=scanner.acquires,
            blocks=scanner.blocks, calls=scanner.calls)
        fe = _effects.FunctionEffects(key=key)
        eff = _effects.EffectScanner(qualname, mf.imports,
                                     cls_mutables.get(cls or "", set()))
        fe.effects = eff.scan(fn)
        fe.resources = _effects._ResourceTracker(fn, qualname).run(
            mf.imports)
        fe.captures = _effects.scan_captures(
            fn, cls_mutables.get(cls or "", set()), enclosing_mutables)
        if any(_effects._is_jit_decorator(d)
               for d in getattr(fn, "decorator_list", ())):
            fe.jitted = True
            fe.jit_line = fn.lineno
        mf.effects[key] = fe
        mf.flows[key] = _dataflow.scan_flow(fn, key, mf.imports, cls)
        mf.threads[key] = _threads.scan_threads(
            fn, key, module, path, cls, qualname, locks, mf.imports)
        name_keys.setdefault(fn.name, []).append(key)

    def iter_defs(body):
        """Def/class statements anywhere in ``body``, descending into
        compound statements (for/if/with/try/match) but never into
        another def or class — a jitted step defined inside an epoch
        loop (the streaming-estimator idiom) is still THIS scope's
        def."""
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                yield node
                continue
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.stmt):
                    yield from iter_defs([child])
                elif isinstance(child, ast.ExceptHandler):
                    yield from iter_defs(child.body)
                elif isinstance(child, ast.match_case):
                    yield from iter_defs(child.body)

    def walk_defs(body, prefix: str, cls: Optional[str],
                  enclosing_mutables: Dict[str, int]):
        for node in iter_defs(body):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                qual = f"{prefix}{node.name}" if prefix else node.name
                scan_fn(node, qual, cls, enclosing_mutables)
                # nested defs get their own facts under a dotted qual;
                # their capture analysis sees THIS function's mutable
                # local bindings
                walk_defs(node.body, qual + ".", cls,
                          _effects._local_mutable_bindings(node))
            elif isinstance(node, ast.ClassDef):
                methods = [m.name for m in node.body
                           if isinstance(m, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))]
                mf.classes[node.name] = methods
                cls_mutables.setdefault(
                    node.name, _effects.mutable_class_attrs(node))
                guards = _class_guards(node)
                if guards:
                    mf.class_guards[node.name] = guards
                walk_defs(node.body, node.name + ".", node.name, {})

    walk_defs(tree.body, "", None, {})
    # jit call forms: jax.jit(step), partial(jax.jit, ...)(step) —
    # mark the named def(s) as jit roots (same resolution as H2)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _effects._jit_call(node):
            args = node.args
            if _effects._dotted(node.func) in _effects._PARTIAL_NAMES:
                args = args[1:]
        elif isinstance(node.func, ast.Call) and \
                _effects._jit_call(node.func):
            # partial(jax.jit, ...)(step): the OUTER call's args hold
            # the traced function
            args = node.args
        else:
            continue
        for arg in args:
            if isinstance(arg, ast.Name):
                for key in name_keys.get(arg.id, ()):
                    mf.effects[key].jitted = True
                    mf.effects[key].jit_line = \
                        mf.effects[key].jit_line or node.lineno
    # captures only mean anything at a jit boundary — dropping the
    # rest keeps the serialized facts (and the result cache) lean
    for fe in mf.effects.values():
        if not fe.jitted:
            fe.captures = []
    mf.functions = [mf.facts[q].qualname for q in mf.facts
                    if "." not in mf.facts[q].qualname]
    mf.module_locks = sorted(locks.module_locks)
    return mf


class CallGraph:
    """The resolved whole-program view over a set of ModuleFacts."""

    def __init__(self, modules: List[ModuleFacts]):
        self.modules = {m.module: m for m in modules}
        #: every function key -> facts
        self.functions: Dict[str, FunctionFacts] = {}
        #: method name -> defining keys across the analyzed set
        self._methods: Dict[str, List[str]] = {}
        #: module -> {function name -> key}
        self._module_fns: Dict[str, Dict[str, str]] = {}
        for m in modules:
            fns: Dict[str, str] = {}
            for key, f in m.facts.items():
                self.functions[key] = f
                qual = f.qualname
                if "." not in qual:
                    fns[qual] = key
                else:
                    cls, meth = qual.rsplit(".", 1)
                    if "." not in cls:   # plain Class.method
                        self._methods.setdefault(meth, []).append(key)
            self._module_fns[m.module] = fns
        self._may_block: Dict[str, Optional[Tuple[str, str]]] = {}
        self._may_acquire: Dict[str, Dict[str, Tuple[str, ...]]] = {}
        self._normalize_lock_ids()

    def _normalize_lock_ids(self) -> None:
        """An imported lock's id carries the import-path module
        (``collective::LAUNCH_LOCK``); the defining module's own id
        carries its display-derived name (``fixtures.collective::…``).
        Remap by unique module suffix so both spellings are ONE lock —
        cross-module lock identity is the whole point of H7. Imported
        CANDIDATES (``?mod::attr`` — a bare imported name used as a
        context manager) confirm against the defining module's
        module-lock table (or a lock-shaped name when the module is
        outside the analyzed set) and DROP otherwise: ``with
        some_imported_cm:`` is not a lock."""
        from sparkdl_tpu.analysis.locks import _LOCKISH_NAME
        cache: Dict[str, Optional[str]] = {}

        def norm(lock: str) -> Optional[str]:
            if lock in cache:
                return cache[lock]
            out: Optional[str] = lock
            candidate = lock.startswith("?")
            mod, sep, attr = lock.lstrip("?").partition("::")
            if sep and mod not in self.modules:
                match = self._match_module(mod)
                if match is not None:
                    mod = match
            if candidate:
                facts = self.modules.get(mod)
                if facts is not None:
                    out = (f"{mod}::{attr}"
                           if attr in facts.module_locks else None)
                else:
                    out = (f"{mod}::{attr}"
                           if _LOCKISH_NAME.search(attr) else None)
            elif sep:
                out = f"{mod}::{attr}"
            cache[lock] = out
            return out

        for f in self.functions.values():
            kept = []
            for acq in f.acquires:
                lock = norm(acq.lock)
                if lock is None:
                    continue
                acq.lock = lock
                acq.held = tuple(h2 for h2 in
                                 (norm(h) for h in acq.held)
                                 if h2 is not None)
                kept.append(acq)
            f.acquires = kept
            for b in f.blocks:
                b.held = tuple(h2 for h2 in (norm(h) for h in b.held)
                               if h2 is not None)
            for c in f.calls:
                c.held = tuple(h2 for h2 in (norm(h) for h in c.held)
                               if h2 is not None)
        # the thread/race facts carry the same lock ids in their
        # region tuples — same normalization, or a candidate spelling
        # ("?mod::attr") would never match its confirmed one and the
        # race rules would see "no common lock" where there is one
        for m in self.modules.values():
            for tf in getattr(m, "threads", {}).values():
                for a in tf.accesses:
                    a.regions = tuple(
                        (lk, ln) for lk, ln in
                        ((norm(lk0), ln0) for lk0, ln0 in a.regions)
                        if lk is not None)
                tf.local_muts = [
                    (n, ln, tuple(h2 for h2 in
                                  (norm(h) for h in held)
                                  if h2 is not None))
                    for n, ln, held in tf.local_muts]

    def _match_module(self, dotted: str) -> Optional[str]:
        """The analyzed module an import path names: exact, else by
        unique dotted-suffix (``from serve import f`` inside a tree
        whose display-derived module is ``fixtures.serve``)."""
        if dotted in self.modules:
            return dotted
        hits = [m for m in self.modules
                if m.endswith("." + dotted) or m == dotted]
        return hits[0] if len(hits) == 1 else None

    # -- resolution ----------------------------------------------------------

    def resolve(self, caller: FunctionFacts, call: CallEvent
                ) -> Optional[str]:
        """The callee's key, or None when lexical resolution cannot
        name exactly one target."""
        mod = self.modules.get(caller.module)
        if call.kind == "self":
            cls = call.qualifier
            key = f"{caller.module}::{cls}.{call.name}"
            if key in self.functions:
                return key
            # inherited method: unique across the analyzed classes
            return self._unique_method(call.name)
        if call.kind == "name":
            key = self._module_fns.get(caller.module, {}).get(call.name)
            if key is not None:
                return key
            if mod is not None:
                src = mod.imports.get(call.name)
                if src is not None:
                    m, _, fn = src.rpartition(".")
                    m = self._match_module(m) if m else None
                    if m is not None:
                        key = f"{m}::{fn}"
                        if key in self.functions:
                            return key
            return None
        if call.kind == "dotted":
            src = self._match_module(call.qualifier)
            if src is not None:
                key = f"{src}::{call.name}"
                if key in self.functions:
                    return key
            return None
        if call.kind == "method":
            return self._unique_method(call.name)
        return None

    def _unique_method(self, name: str) -> Optional[str]:
        keys = self._methods.get(name, [])
        if len(keys) == 1:
            return keys[0]
        return None

    # -- transitive facts ----------------------------------------------------

    def may_block(self, key: str, depth: int = MAX_DEPTH,
                  _seen: Optional[Set[str]] = None
                  ) -> Optional[Tuple[str, str]]:
        """(witness chain, blocking-op description) when a call into
        ``key`` can block the calling thread; None otherwise. The chain
        is " -> "-joined qualified names ending at the blocking op."""
        if key in self._may_block:
            return self._may_block[key]
        f = self.functions.get(key)
        if f is None or depth <= 0:
            return None
        seen = _seen if _seen is not None else set()
        if key in seen:
            return None
        seen.add(key)
        result: Optional[Tuple[str, str]] = None
        if f.blocks:
            b = f.blocks[0]
            result = (self.short(key), b.what)
        else:
            for call in f.calls:
                target = self.resolve(f, call)
                if target is None or target == key:
                    continue
                sub = self.may_block(target, depth - 1, seen)
                if sub is not None:
                    result = (f"{self.short(key)} -> {sub[0]}", sub[1])
                    break
        seen.discard(key)
        if _seen is None or result is not None or depth == MAX_DEPTH:
            self._may_block[key] = result
        return result

    def may_acquire(self, key: str, depth: int = MAX_DEPTH,
                    _seen: Optional[Set[str]] = None
                    ) -> Dict[str, Tuple[str, ...]]:
        """lock id -> witness chain (qualified names, " -> "-joined)
        for every lock a call into ``key`` may end up acquiring."""
        if key in self._may_acquire:
            return self._may_acquire[key]
        f = self.functions.get(key)
        if f is None or depth <= 0:
            return {}
        seen = _seen if _seen is not None else set()
        if key in seen:
            return {}
        seen.add(key)
        out: Dict[str, Tuple[str, ...]] = {}
        for acq in f.acquires:
            out.setdefault(acq.lock, (self.short(key),))
        for call in f.calls:
            target = self.resolve(f, call)
            if target is None or target == key:
                continue
            for lock, chain in self.may_acquire(
                    target, depth - 1, seen).items():
                out.setdefault(lock,
                               (self.short(key),) + chain)
        seen.discard(key)
        if _seen is None or depth == MAX_DEPTH:
            self._may_acquire[key] = out
        return out

    # -- display -------------------------------------------------------------

    @staticmethod
    def short(key: str) -> str:
        """`module::Qual` with the package prefix trimmed for humans."""
        mod, _, qual = key.partition("::")
        mod = mod[len("sparkdl_tpu."):] if \
            mod.startswith("sparkdl_tpu.") else mod
        return f"{mod}:{qual}" if qual else mod


def parse_file(path: str) -> Optional[ast.Module]:
    try:
        with open(path, encoding="utf-8") as f:
            return ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return None


def build_graph(paths: List[str]) -> CallGraph:
    """Convenience for tests/tools: parse + scan + assemble."""
    mods = []
    for path in paths:
        tree = parse_file(path)
        if tree is not None:
            mods.append(scan_module(tree, os.path.relpath(path)
                                    if not path.startswith("..")
                                    else path))
    return CallGraph(mods)
