#!/usr/bin/env python
"""Gate a fresh bench JSON against the committed schema.

The bench's JSON line is a driver contract: round-over-round tooling
reads its keys by name, and a refactor that drops or retypes one makes
the trajectory silently lose a column (the schema asserts in
tools/ci.sh step 4 catch a fixed list; this tool checks every key of
the committed reference, ``tools/bench_schema.json`` — keys and
placeholder values of the right JSON type, no measurements). Rules:

* every key present in the reference must be present in the fresh
  output with the same JSON type (recursing through nested objects;
  ``int`` vs ``float`` are both "number");
* ``null`` on either side is a wildcard — platform-dependent sections
  (TPU-only shapes on a CPU run, and vice versa) legitimately go null;
* NEW keys in the fresh output are allowed (schemas grow), but the
  fresh output must then carry ``schema_version`` (an int >= 1) so
  readers can key off it — bench.py emits it;
* dynamic-content objects (the obs registry snapshot) are compared by
  type only, not by key set — their keys depend on what ran.

Either input is a bench JSON file, or text whose last parsable line is
one.

Usage::

    python tools/bench_compare.py FRESH.json tools/bench_schema.json

Exit 0 on a compatible schema, 1 on drift, 2 on usage/IO errors.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

#: nested objects whose KEYS vary run-to-run (only their type is
#: checked): the registry snapshot depends on which subsystems ran,
#: memory stats on the backend, the autotune block's
#: converged-config / decision detail on which targets and knobs the
#: controller actually touched that round, the tails block's phase
#: breakdown (and null p50/p99) on which requests the serve pass
#: actually recorded, the slo block's objectives on the env's
#: objective config, and the resilience block's per-site counts /
#: circuit state on whether the round armed a fault drill, and the
#: bound block's window/ceilings on what the ledger measured and
#: which probe produced the ceilings that round
#: ... and the compile block's per-function table on which programs
#: the round actually compiled (obs/compile_log.py), and the
#: pipeline_overlap block's mode/worker shape on the measuring host's
#: cores and start-method support (data/pipeline.py), and the
#: ship_ring block's ring depth / hit and byte tallies on the
#: measuring host's corpus shape (runtime/runner.py InfeedRing),
#: and the input_service block's rows/s and snapshot tallies on the
#: measuring host's cores and disk (sparkdl_tpu/inputsvc/),
#: and the fleet block's swap/warm-start/packing numbers on the
#: measuring host's devices and whether the backend can serialize
#: executables at all (sparkdl_tpu/fleet/)
DYNAMIC_KEYS = {"registry", "memory_stats", "active_sources",
                "autotune", "tails", "slo", "resilience", "bound",
                "compile", "pipeline_overlap", "ship_ring",
                "input_service", "fleet"}


def _from_lines(text: str) -> Optional[dict]:
    """The last line that parses as a bench dict (bench.py prints ONE
    JSON line, but logs may precede it)."""
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(d, dict) and "metric" in d:
            return d
    return None


def load_bench_json(path: str) -> Optional[dict]:
    """The bench dict from ``path``: a bench JSON file, or text whose
    last parsable line is one. None when nothing usable is found."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    try:
        d = json.loads(text)
    except json.JSONDecodeError:
        return _from_lines(text)
    if not isinstance(d, dict):
        return None
    return d if "metric" in d else None


def _type_of(v) -> str:
    # bool FIRST: it subclasses int, and a True where a number belongs
    # is exactly the retyping this gate exists to catch
    if v is None:
        return "null"
    if isinstance(v, bool):
        return "bool"
    if isinstance(v, (int, float)):
        return "number"
    if isinstance(v, str):
        return "string"
    if isinstance(v, list):
        return "array"
    if isinstance(v, dict):
        return "object"
    return type(v).__name__


def compare_schema(ref: dict, fresh: dict, prefix: str = ""
                   ) -> List[str]:
    """Drift report: missing/retyped keys, reference → fresh."""
    errors: List[str] = []
    for key, rv in ref.items():
        label = f"{prefix}{key}"
        if key not in fresh:
            errors.append(f"missing key: {label!r} (present in the "
                          "committed reference)")
            continue
        fv = fresh[key]
        if rv is None or fv is None:
            continue    # platform-dependent null — wildcard
        rt, ft = _type_of(rv), _type_of(fv)
        if rt != ft:
            errors.append(f"type drift at {label!r}: reference {rt}, "
                          f"fresh {ft}")
            continue
        if rt == "object" and key not in DYNAMIC_KEYS:
            errors.extend(compare_schema(rv, fv, prefix=f"{label}."))
    return errors


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python tools/bench_compare.py",
        description="validate a fresh bench JSON against the committed "
                    "schema (module docstring for the rules)")
    parser.add_argument("fresh", help="fresh bench output (JSON file, "
                                      "last parsable line wins)")
    parser.add_argument("reference",
                        help="the committed schema "
                             "(tools/bench_schema.json)")
    args = parser.parse_args(argv)

    try:
        fresh = load_bench_json(args.fresh)
        ref = load_bench_json(args.reference)
    except OSError as e:
        print(f"bench_compare: cannot read input: {e}", file=sys.stderr)
        return 2
    if fresh is None:
        print(f"bench_compare: {args.fresh}: no bench JSON line found",
              file=sys.stderr)
        return 2
    if ref is None:
        print(f"bench_compare: {args.reference}: no usable reference "
              "schema", file=sys.stderr)
        return 2
    ref_path = args.reference

    errors = compare_schema(ref, fresh)
    sv = fresh.get("schema_version")
    if not (isinstance(sv, int) and not isinstance(sv, bool)
            and sv >= 1):
        errors.append(
            f"fresh output must carry schema_version (int >= 1), "
            f"got {sv!r}")
    if errors:
        for e in errors:
            print(f"bench_compare: DRIFT: {e}")
        print(f"bench_compare: {len(errors)} schema error(s) vs "
              f"{ref_path}", file=sys.stderr)
        return 1
    print(json.dumps({
        "bench_compare": "ok",
        "reference": ref_path,
        "reference_keys": len(ref),
        "fresh_keys": len(fresh),
        "schema_version": sv,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
