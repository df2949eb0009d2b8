"""H9 — contract drift: what the code publishes vs what the docs table.

The observability surface IS a contract: registry keys scrape to
Prometheus series, span lanes are how an operator reads a trace, env
vars are the ops interface, ``/statusz`` fields feed dashboards. The
docs tables (docs/OBSERVABILITY.md, docs/SERVING.md,
docs/PERFORMANCE.md — plus README.md and the other docs for env vars)
promise those names; nothing has enforced the promise, and every PR so
far re-synced the tables by hand. H9 cross-checks BOTH directions:

* a name the code publishes but no doc table carries → the finding
  points at the publish site and names the table to edit;
* a name a doc table carries but the code no longer publishes → the
  finding points at the doc row (stale docs are worse than none — an
  operator greps for a key that no longer exists mid-incident).

What counts as "published" (lexical, same contract as H1–H6):

* **registry keys** — string/f-string names in
  ``*.counter(...)``/``*.gauge(...)``/``*.reservoir(...)`` calls;
  f-string holes become ``*`` segments. A publish through a variable
  (the ``RunnerMetrics.publish`` loop idiom) falls back to collecting
  the dotted string constants of the enclosing function.
* **span lanes** — ``lane="..."`` constants (plus the tracer's
  internal positional ``_record(name, lane, ...)`` form).
* **env vars** — ``SPARKDL_TPU_*`` string constants outside
  docstrings; the doc corpus for these is every ``docs/*.md`` plus
  ``README.md``, and the code corpus additionally text-scans the repo
  root's driver scripts (tools/, examples/) so a var documented for a
  script doesn't read as stale.
* **/statusz fields** — the top-level keys of the dict
  ``obs/export.py::TelemetryServer._statusz`` returns, against
  SERVING.md's field table (first path segment; ``servers[].…`` rows
  anchor ``servers``).

Doc tables parse from GitHub-flavored markdown: the first column of
any table whose header cell is ``key`` (registry), ``lane`` columns
anywhere, and the ``field`` table (statusz). ``<name>``/``<objective>``
placeholders and f-string holes both normalize to ``*``; match is
pattern OVERLAP (some concrete name satisfies both), so the docs'
``serve.*`` row covers the code's enumerated ``serve.…`` keys and vice
versa.
"""

from __future__ import annotations

import ast
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from sparkdl_tpu.analysis.findings import Finding

_ENV_RE = re.compile(r"\bSPARKDL_TPU_[A-Z0-9_]+\b")
_KEYISH = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_*]+)+$")
_BACKTICK = re.compile(r"`([^`]+)`")

#: the three tables H9 gates (named in findings so the fix is obvious)
REGISTRY_TABLE = "registry-key table (docs/OBSERVABILITY.md / docs/SERVING.md)"
LANE_TABLE = "span-lane table (docs/OBSERVABILITY.md)"
STATUSZ_TABLE = "/statusz field table (docs/SERVING.md)"

#: lanes never passed explicitly (the span() default) — not a contract
_IGNORED_LANES = {"host"}


@dataclass
class Publish:
    """One published name with its source location."""

    name: str               # pattern; '*' segments for dynamic parts
    path: str
    line: int


@dataclass
class CodeSurface:
    """Everything the analyzed code publishes."""

    registry: List[Publish] = field(default_factory=list)
    lanes: List[Publish] = field(default_factory=list)
    env: List[Publish] = field(default_factory=list)
    statusz: List[Publish] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {k: [[p.name, p.path, p.line] for p in getattr(self, k)]
                for k in ("registry", "lanes", "env", "statusz")}

    @classmethod
    def from_dict(cls, d: dict) -> "CodeSurface":
        s = cls()
        for k in ("registry", "lanes", "env", "statusz"):
            getattr(s, k).extend(
                Publish(e[0], e[1], e[2]) for e in d.get(k, []))
        return s

    def merge(self, other: "CodeSurface") -> None:
        for k in ("registry", "lanes", "env", "statusz"):
            getattr(self, k).extend(getattr(other, k))


# ---------------------------------------------------------------------------
# code-side extraction


def _fstring_pattern(node: ast.JoinedStr) -> Optional[str]:
    parts: List[str] = []
    for v in node.values:
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            parts.append(v.value)
        elif isinstance(v, ast.FormattedValue):
            parts.append("*")
        else:
            return None
    return "".join(parts)


def _docstring_nodes(tree: ast.Module) -> Set[int]:
    """ids of Constant nodes that are docstrings (skipped by the env
    scan — prose mentions are documentation, not publishes)."""
    out: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant) and isinstance(
                    body[0].value.value, str):
                out.add(id(body[0].value))
    return out


_METRIC_FACTORIES = {"counter", "gauge", "reservoir"}


class _SurfaceVisitor(ast.NodeVisitor):
    def __init__(self, path: str, tree: ast.Module,
                 surface: CodeSurface):
        self.path = path
        self.surface = surface
        self._doc_ids = _docstring_nodes(tree)
        self._fn_stack: List[ast.AST] = []

    def visit_FunctionDef(self, node):
        self._fn_stack.append(node)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Constant(self, node: ast.Constant):
        if isinstance(node.value, str) and id(node) not in self._doc_ids:
            for m in _ENV_RE.finditer(node.value):
                self.surface.env.append(
                    Publish(m.group(0), self.path, node.lineno))

    def visit_Call(self, node: ast.Call):
        # registry keys
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _METRIC_FACTORIES:
            name_arg = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "name"),
                None)
            self._record_metric_name(name_arg, node)
        # span lanes: span(..., lane="x") and _record(name, "lane", ..)
        fn_name = None
        if isinstance(node.func, ast.Name):
            fn_name = node.func.id
        elif isinstance(node.func, ast.Attribute):
            fn_name = node.func.attr
        if fn_name == "span":
            for kw in node.keywords:
                if kw.arg == "lane" and isinstance(
                        kw.value, ast.Constant) and isinstance(
                        kw.value.value, str):
                    self._lane(kw.value.value, node.lineno)
        elif fn_name == "_record" and len(node.args) >= 2 and \
                isinstance(node.args[1], ast.Constant) and \
                isinstance(node.args[1].value, str):
            self._lane(node.args[1].value, node.lineno)
        self.generic_visit(node)

    def _lane(self, lane: str, line: int):
        if lane not in _IGNORED_LANES:
            self.surface.lanes.append(Publish(lane, self.path, line))

    def _record_metric_name(self, name_arg, call: ast.Call):
        if name_arg is None:
            return
        if isinstance(name_arg, ast.Constant) and isinstance(
                name_arg.value, str):
            self.surface.registry.append(
                Publish(name_arg.value, self.path, call.lineno))
            return
        if isinstance(name_arg, ast.JoinedStr):
            pat = _fstring_pattern(name_arg)
            if pat is not None:
                self.surface.registry.append(
                    Publish(pat, self.path, call.lineno))
                return
        # dynamic name (publish-loop idiom): fall back to the dotted
        # string constants of the enclosing function — the key tables
        # those loops iterate are module-local literals in this repo
        if self._fn_stack:
            for node in ast.walk(self._fn_stack[-1]):
                if isinstance(node, ast.Constant) and isinstance(
                        node.value, str) and _KEYISH.match(node.value):
                    self.surface.registry.append(Publish(
                        node.value, self.path, node.lineno))
                elif isinstance(node, ast.JoinedStr):
                    pat = _fstring_pattern(node)
                    if pat and _KEYISH.match(pat):
                        self.surface.registry.append(Publish(
                            pat, self.path, node.lineno))


def _extract_statusz(tree: ast.Module, path: str,
                     surface: CodeSurface) -> None:
    """Top-level keys of the dict `_statusz` returns (obs/export.py)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == "_statusz":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Return) and isinstance(
                        sub.value, ast.Dict):
                    for k in sub.value.keys:
                        if isinstance(k, ast.Constant) and isinstance(
                                k.value, str):
                            surface.statusz.append(
                                Publish(k.value, path, k.lineno))


def extract_file_surface(path: str, tree: ast.Module) -> CodeSurface:
    """One module's published surface (cache-serializable)."""
    surface = CodeSurface()
    _SurfaceVisitor(path, tree, surface).visit(tree)
    if path.replace("\\", "/").endswith("obs/export.py"):
        _extract_statusz(tree, path, surface)
    return surface


def extract_surface(files: List[Tuple[str, ast.Module]]) -> CodeSurface:
    surface = CodeSurface()
    for path, tree in files:
        surface.merge(extract_file_surface(path, tree))
    return surface


# ---------------------------------------------------------------------------
# docs-side extraction


@dataclass
class DocName:
    name: str               # normalized pattern
    path: str
    line: int


@dataclass
class DocSurface:
    registry: List[DocName] = field(default_factory=list)
    lanes: List[DocName] = field(default_factory=list)
    env: List[DocName] = field(default_factory=list)
    statusz: List[DocName] = field(default_factory=list)


def _expand_cell_tokens(cell: str, prev: Optional[str]) -> List[str]:
    """Backticked tokens of one table cell, with `{a,b}` brace sets
    expanded, `<x>` placeholders → `*`, and a leading-dot token
    continuing the previous token's prefix (`slo.<o>.burn_rate` /
    `.budget_remaining`)."""
    out: List[str] = []
    for raw in _BACKTICK.findall(cell):
        tok = raw.strip()
        if not tok or " " in tok:
            continue
        if tok.startswith("."):
            base = out[-1] if out else prev
            if base is None:
                continue
            tok = base.rsplit(".", 1)[0] + tok
        # brace expansion: a.{x,y}.z -> a.x.z, a.y.z
        m = re.search(r"\{([^{}]+)\}", tok)
        variants = ([tok.replace(m.group(0), alt.strip())
                     for alt in m.group(1).split(",")] if m else [tok])
        for v in variants:
            v = re.sub(r"<[^<>]+>", "*", v)
            out.append(v)
    return out


def _iter_tables(path: str):
    """(header_cells, [(line_no, row_cells), ...]) per markdown table."""
    with open(path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    i = 0
    while i < len(lines):
        line = lines[i].strip()
        if line.startswith("|") and i + 1 < len(lines) and \
                set(lines[i + 1].strip()) <= set("|-: "):
            header = [c.strip().lower()
                      for c in line.strip("|").split("|")]
            rows = []
            j = i + 2
            while j < len(lines) and lines[j].strip().startswith("|"):
                cells = [c.strip()
                         for c in lines[j].strip().strip("|").split("|")]
                rows.append((j + 1, cells))
                j += 1
            yield header, rows
            i = j
        else:
            i += 1


def extract_docs(docs_files: List[str]) -> DocSurface:
    docs = DocSurface()
    for path in docs_files:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for idx, line in enumerate(text.splitlines(), start=1):
            for m in _ENV_RE.finditer(line):
                docs.env.append(DocName(m.group(0), path, idx))
        for header, rows in _iter_tables(path):
            first = header[0] if header else ""
            lane_cols = [k for k, h in enumerate(header) if h == "lane"]
            for line_no, cells in rows:
                prev = None
                if first == "key" and cells:
                    for tok in _expand_cell_tokens(cells[0], prev):
                        docs.registry.append(DocName(tok, path, line_no))
                        prev = tok
                if first == "field" and cells:
                    # a dotted first token makes the rest of the cell
                    # sub-paths of it (`servers[].models.<n>.collective`
                    # / `chunk` / `runner`); an undotted first token
                    # makes the cell a list of sibling top-level
                    # fields (`uptime_s`, `pid`, `platform`)
                    toks = _expand_cell_tokens(cells[0], prev)
                    if toks:
                        anchor = ([toks[0]] if "." in toks[0]
                                  else [t for t in toks
                                        if "." not in t])
                        for tok in anchor:
                            root = tok.split(".")[0].replace("[]", "")
                            docs.statusz.append(
                                DocName(root, path, line_no))
                for k in lane_cols:
                    if k < len(cells):
                        for tok in _expand_cell_tokens(cells[k], None):
                            docs.lanes.append(
                                DocName(tok, path, line_no))
    return docs


# ---------------------------------------------------------------------------
# pattern matching


def _overlap(a: List[str], b: List[str]) -> bool:
    """Can some concrete dotted name match both patterns? `*` matches
    one segment, a TRAILING `*` one-or-more."""
    if not a and not b:
        return True
    if not a or not b:
        return False
    a0, b0 = a[0], b[0]
    if a0 == "*" and len(a) == 1:
        return len(b) >= 1
    if b0 == "*" and len(b) == 1:
        return len(a) >= 1
    if a0 == "*" or b0 == "*" or a0 == b0 or \
            _seg_overlap(a0, b0):
        return _overlap(a[1:], b[1:])
    return False


def _seg_overlap(a: str, b: str) -> bool:
    """Within-segment wildcards (`inflight*`)."""
    if "*" not in a and "*" not in b:
        return a == b
    ra = re.escape(a).replace(r"\*", ".*")
    rb = re.escape(b).replace(r"\*", ".*")
    return bool(re.fullmatch(ra, b.replace("*", "x"))
                or re.fullmatch(rb, a.replace("*", "x")))


def names_overlap(a: str, b: str) -> bool:
    return _overlap(a.split("."), b.split("."))


# ---------------------------------------------------------------------------
# the rule


def find_docs(start: str) -> Optional[str]:
    """The repo docs dir governing ``start``: walk up for a directory
    holding docs/OBSERVABILITY.md + docs/SERVING.md +
    docs/PERFORMANCE.md. None → H9 is skipped (fixture trees)."""
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    for _ in range(8):
        docs = os.path.join(cur, "docs")
        if all(os.path.isfile(os.path.join(docs, n)) for n in
               ("OBSERVABILITY.md", "SERVING.md", "PERFORMANCE.md")):
            return cur
        nxt = os.path.dirname(cur)
        if nxt == cur:
            break
        cur = nxt
    return None


def _doc_corpus(root: str) -> List[str]:
    out = sorted(glob.glob(os.path.join(root, "docs", "*.md")))
    readme = os.path.join(root, "README.md")
    if os.path.isfile(readme):
        out.append(readme)
    return out


def _script_env_tokens(root: str) -> Set[str]:
    """Env vars read by the repo's driver scripts (tools/*,
    examples/*) — text scan only; they are part of the env contract's
    CODE side even when the lint targets don't include them."""
    tokens: Set[str] = set()
    paths = glob.glob(os.path.join(root, "tools", "*"))
    paths += glob.glob(os.path.join(root, "examples", "*"))
    for path in paths:
        try:
            with open(path, encoding="utf-8", errors="ignore") as f:
                tokens.update(_ENV_RE.findall(f.read()))
        except OSError:
            continue
    return tokens


def check_h9(files: List[Tuple[str, ast.Module]],
             docs_root: Optional[str] = None) -> List[Finding]:
    """Cross-check the analyzed files' published surface against the
    doc tables under ``docs_root`` (auto-detected from the first file
    when None)."""
    if not files:
        return []
    return check_surface(extract_surface(files),
                         [p for p, _ in files], docs_root)


def check_surface(surface: CodeSurface, file_paths: List[str],
                  docs_root: Optional[str] = None) -> List[Finding]:
    """The H9 verdict over an (already-extracted, possibly cached)
    published surface. Doc-side ("documented but gone") checks only
    run when the analyzed set includes the package's obs layer — a
    partial lint (one file, tools/ only) must not misread the docs as
    stale."""
    if not file_paths:
        return []
    if docs_root is None:
        docs_root = find_docs(file_paths[0])
    if docs_root is None:
        return []
    docs = extract_docs(_doc_corpus(docs_root))
    findings: List[Finding] = []
    full_view = any(p.replace("\\", "/").endswith("obs/registry.py")
                    for p in file_paths)

    def gate(published: List[Publish], documented: List[DocName],
             table: str, kind: str, match=names_overlap,
             doc_side: bool = True):
        for pub in published:
            if not any(match(pub.name, d.name) for d in documented):
                findings.append(Finding(
                    rule="H9", path=pub.path, line=pub.line, col=0,
                    message=(
                        f"{kind} `{pub.name}` is published here but "
                        f"missing from the {table} — document it "
                        "there (the docs tables are the operator "
                        "contract), or suppress with `# sparkdl-lint: "
                        "allow[H9] -- <why it is not part of the "
                        "contract>`")))
        if not (doc_side and full_view):
            return
        pub_names = [p.name for p in published]
        for d in documented:
            if not any(match(n, d.name) for n in pub_names):
                findings.append(Finding(
                    rule="H9", path=d.path, line=d.line, col=0,
                    message=(
                        f"documented {kind} `{d.name}` is no longer "
                        f"published by the code — remove or update "
                        f"this row of the {table} (stale docs send an "
                        "operator grepping for a name that does not "
                        "exist)")))

    gate(surface.registry, docs.registry, REGISTRY_TABLE,
         "registry key")
    gate(surface.lanes, docs.lanes, LANE_TABLE, "span lane",
         match=lambda a, b: a == b)
    gate(surface.statusz, docs.statusz, STATUSZ_TABLE,
         "/statusz field", match=lambda a, b: a == b)
    # env vars: docs corpus is ALL prose (not just tables); the code
    # corpus adds the driver scripts' reads
    script_tokens = _script_env_tokens(docs_root)
    doc_env = {d.name for d in docs.env}
    seen_env: Set[str] = set()
    for pub in surface.env:
        if pub.name in seen_env:
            continue
        seen_env.add(pub.name)
        if pub.name not in doc_env:
            findings.append(Finding(
                rule="H9", path=pub.path, line=pub.line, col=0,
                message=(
                    f"env var `{pub.name}` is read here but "
                    "documented nowhere under docs/ or README.md — "
                    "add it to the relevant doc (env vars are the ops "
                    "interface), or suppress with `# sparkdl-lint: "
                    "allow[H9] -- <why>`")))
    if full_view:
        code_env = {p.name for p in surface.env} | script_tokens
        reported: Set[str] = set()
        for d in docs.env:
            if d.name in code_env or d.name in reported:
                continue
            reported.add(d.name)
            findings.append(Finding(
                rule="H9", path=d.path, line=d.line, col=0,
                message=(
                    f"documented env var `{d.name}` is read by "
                    "nothing in the package or driver scripts — "
                    "remove or update the mention (a documented knob "
                    "that does nothing is an operator trap)")))
    findings.sort(key=lambda f: (f.path, f.line))
    return findings
