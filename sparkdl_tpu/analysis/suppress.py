"""Suppression config for sparkdl-lint: inline annotations + the
built-in drain-path allowlist.

Two ways to accept a finding, both carrying a justification so no
suppression is ever silent:

* **inline** — a ``# sparkdl-lint: allow[H1]`` comment, either trailing
  on the flagged line or standalone on the line directly above it.
  Multiple rules separate with commas (``allow[H1,H4]``); ``allow[*]``
  accepts every rule on that line. Everything after ``--`` is the
  justification, echoed in ``--show-suppressed`` output::

      jax.device_get(losses)  # sparkdl-lint: allow[H1] -- epoch drain

* **allowlist** — :data:`DEFAULT_ALLOWLIST` entries naming a
  ``(path suffix, qualname prefix)`` pair per rule: code whose entire
  JOB is the thing the rule bans (SlabSink's drain IS the device_get
  the rest of the ship path must not do; the measure tools exist to
  time transfers). Keep this list short — anything not structurally a
  drain should suppress inline, at the use site, where review sees it.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Optional, Set, Tuple

_SUPPRESS_RE = re.compile(
    r"#\s*sparkdl-lint:\s*allow\[([A-Za-z0-9*,\s]+)\]"
    r"(?:\s*--\s*(?P<why>.*?))?\s*$")


@dataclass(frozen=True)
class AllowEntry:
    """One allowlisted region: a path suffix plus an optional dotted
    qualname prefix (empty = the whole file). ``why`` is mandatory —
    an allowlist entry without a reason is a convention, and the whole
    point of this package is that conventions drift."""

    path_suffix: str
    qualname: str
    why: str

    def matches(self, path: str, qualname: str) -> bool:
        norm = path.replace("\\", "/")
        if not norm.endswith(self.path_suffix):
            return False
        if not self.qualname:
            return True
        return (qualname == self.qualname
                or qualname.startswith(self.qualname + "."))


#: The drain-path set: the ONLY places allowed to synchronize
#: device→host without an inline justification — plus the two
#: structurally-intentional holds the whole-program rules would
#: otherwise flag (H8: the dispatcher's coalescing wait IS the
#: batching window) and the measurement CLIs whose entire job is the
#: banned operation.
DEFAULT_ALLOWLIST: Dict[str, Tuple[AllowEntry, ...]] = {
    "H1": (
        AllowEntry(
            "sparkdl_tpu/obs/trace.py", "timed_device_get",
            "THE drain, relocated from SlabSink.write so the sync is "
            "observable: every runner funnels results to host "
            "through this one device_get, spanned on the 'device' "
            "lane and timed into transfer_wait_seconds"),
        AllowEntry(
            "sparkdl_tpu/utils/measure.py", "",
            "measurement tools: forcing + timing transfers is their "
            "entire job (forced-sync methodology, VERDICT r1 weak #3)"),
        AllowEntry(
            "tools/train_testnet_artifact.py", "main",
            "one-shot artifact trainer: the end-of-fit parameter "
            "drain IS the artifact write (nothing downstream to "
            "overlap with)"),
    ),
    "H14": (
        AllowEntry(
            "sparkdl_tpu/obs/trace.py", "timed_device_get",
            "THE sanctioned hot-path drain (the H1 entry's "
            "whole-program twin): every runner funnels device "
            "results to host through this one sync, spanned and "
            "timed — a hot path may materialize HERE and nowhere "
            "else"),
    ),
    "H17": (
        AllowEntry(
            "sparkdl_tpu/obs/registry.py", "Reservoir._offer_exemplar",
            "caller-holds contract: observe() wraps every call in "
            "self._lock (the same decision the method's inline H3 "
            "suppressions document, lifted to one entry instead of "
            "five line annotations); the private-helper shape is "
            "runtime-asserted elsewhere under SPARKDL_TPU_SANITIZE=1"),
    ),
    "H8": (
        AllowEntry(
            "sparkdl_tpu/serve/batching.py", "RequestQueue.collect",
            "the dispatcher's intentional Condition.wait: the "
            "coalescing window IS the product (latency deliberately "
            "traded for batch fill, docs/SERVING.md) — wait() "
            "RELEASES the queue mutex while blocked, so producers "
            "keep admitting; deadline clipping bounds the sleep"),
    ),
}


class SuppressionIndex:
    """Per-file map of line → (rules, justification) built from the
    raw source, consulted once per finding.

    A trailing annotation binds to its own line; a standalone
    annotation (the line holds nothing but the comment) binds to the
    next non-blank, non-comment line below — the first line of the
    statement it precedes.
    """

    def __init__(self, source: str):
        self._by_line: Dict[int, Tuple[Set[str], str]] = {}
        lines = source.splitlines()
        for i, raw in enumerate(lines, start=1):
            m = _SUPPRESS_RE.search(raw)
            if not m:
                continue
            rules = {r.strip().upper() for r in m.group(1).split(",")
                     if r.strip()}
            why = (m.group("why") or "").strip() or "annotated, no reason"
            stripped = raw.strip()
            target = i
            if stripped.startswith("#"):
                # standalone: walk down to the code line it precedes
                j = i + 1
                while j <= len(lines) and (
                        not lines[j - 1].strip()
                        or lines[j - 1].strip().startswith("#")):
                    j += 1
                target = j
            have = self._by_line.get(target)
            if have:
                rules = rules | have[0]
                why = have[1] if have[1] != "annotated, no reason" else why
            self._by_line[target] = (rules, why)

    def lookup(self, rule: str, line: int) -> Optional[str]:
        """The justification if ``rule`` is suppressed at ``line``."""
        hit = self._by_line.get(line)
        if hit is None:
            return None
        rules, why = hit
        if rule.upper() in rules or "*" in rules:
            return why
        return None


def allowlisted(rule: str, path: str, qualname: str,
                allowlist: Optional[Dict[str, Tuple[AllowEntry, ...]]]
                = None) -> Optional[str]:
    """The allowlist justification for (rule, location), or None."""
    table = DEFAULT_ALLOWLIST if allowlist is None else allowlist
    for entry in table.get(rule.upper(), ()):
        if entry.matches(path, qualname):
            where = entry.path_suffix
            if entry.qualname:
                where += f"::{entry.qualname}"
            return f"allowlist[{where}] -- {entry.why}"
    return None
