"""From the program's own spans and the device trace, on one clock, to what the host
was doing in each partition-boundary gap, the host's cost of one enqueue, the
compiles inside the window and the device time of each named block.

The program's spans are ``sparkdl_tpu.obs.trace`` records. Their clock is
``time.perf_counter()``, and so is ``harness.Tracer.zero``, the reading taken where
``start_trace`` is called: ``start - zero`` puts a span on the trace's clock. Here a
span is a dict ``{"name", "start", "end", "id", "parent"}`` with seconds on that
clock (negative before the profiler started), as ``program_view`` makes them and
as ``tests/data/trace_boundary.program_spans.json`` keeps them. Pure functions;
only ``instruction_seconds`` touches JAX, to read the ``.xplane.pb``."""

from __future__ import annotations

import bisect
import re
import statistics

from benchmarks.tracing import _DEVICE_PLANE

RUN_SPANS = ("runner.run", "runner.run_sharded")
PARTS = ("drain_tail", "handoff", "refill_host", "first_step_lag")
#: how far the two clocks may disagree (PR 25 read 0.05 ms between the two zeros)
CLOCK_SLACK_S = 0.2e-3

_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def spans_on_trace_clock(records, zero: float) -> list:
    """``SpanRecord``s as the dicts this module reads, oldest first."""
    spans = [{"name": r.name, "start": r.start - zero, "end": r.end - zero,
              "id": r.span_id, "parent": r.parent_id} for r in records]
    return sorted(spans, key=lambda s: s["start"])


def boundary_gaps(gaps, spans, window) -> list:
    """One dict per boundary gap: an idle gap of the device, strictly inside the
    traced window, in which a ``runner.run``/``runner.run_sharded`` span begins.
    ``gaps`` are (start_s, end_s) from the window's start, as
    ``tracing.DeviceTrace.gaps`` holds them; ``window`` is (start_s, end_s) on the
    trace's clock. Each gap is cut at four clock readings in order:

    * the gap's start, when the last operation of a step ended on the device;
    * the end of the run span that was open then (``drain_tail``: the readback of
      the last batch, the slab, the counters);
    * the start of the run span that begins in the gap (``handoff``: append the
      column, the engine's stream, the consumer, ``source.load``, Arrow to tensor);
    * the end of that run's first ``dispatch`` span (``refill_host``: checks,
      ``pad_stage``, placement, the enqueue), or the gap's end where the device
      started before the enqueue returned;
    * the gap's end, the first operation of the next step (``first_step_lag``: the
      upload and the launch, under no host span).

    So the parts sum to the gap. A part below minus ``CLOCK_SLACK_S`` is an error
    in the spans or the clocks and raises; within it, it is clamped to 0. A run
    span with no ``dispatch`` child raises: it does not read 0."""
    w0, w1 = window
    runs = [s for s in spans if s["name"] in RUN_SPANS]
    first_dispatch: dict = {}
    for s in spans:
        if s["name"] == "dispatch" and s["parent"] not in first_dispatch:
            first_dispatch[s["parent"]] = s  # spans are sorted by start
    out = []
    for a, b in gaps:
        if a <= 0.0 or b >= w1 - w0:
            continue  # cut by the window's edge: not a whole gap
        t0, t4 = a + w0, b + w0
        begun = [r for r in runs if t0 <= r["start"] <= t4]
        if not begun:
            continue
        nxt = begun[0]
        before = [r for r in runs if r["start"] < t0]
        if not before:
            continue  # the gap before the first run the spans hold
        prev = before[-1]
        dispatch = first_dispatch.get(nxt["id"])
        if dispatch is None:
            raise ValueError(
                f"the {nxt['name']} span {nxt['id']} at {nxt['start']:.6f} s has no "
                "dispatch span under it: the boundary cannot be cut")
        t1, t2, t3 = prev["end"], nxt["start"], min(dispatch["end"], t4)
        cuts = (t0, t1, t2, t3, t4)
        parts = {}
        for name, lo, hi in zip(PARTS, cuts, cuts[1:]):
            if hi - lo < -CLOCK_SLACK_S:
                raise ValueError(
                    f"gap at {t0:.6f} s: {name} is {(hi - lo) * 1e3:.3f} ms; the spans "
                    "and the device trace are not on one clock, or a span is missing")
            parts[name] = max(hi - lo, 0.0)
        out.append(dict(parts, gap=t4 - t0, start=t0, run=nxt["id"]))
    return out


def boundary_parts(boundaries) -> dict | None:
    """Medians over the boundary gaps, in seconds, with their count: the result
    line's ``breakdown.boundary_parts``. None where there is no boundary."""
    if not boundaries:
        return None
    parts = {k: statistics.median(g[k] for g in boundaries) for k in ("gap",) + PARTS}
    parts["gaps"] = len(boundaries)
    return parts


def span_lengths(spans, name: str, start_from: float = float("-inf"),
                 start_before: float = float("inf")) -> list:
    """Lengths (s) of the spans called ``name`` that begin in [start_from, start_before)."""
    return [s["end"] - s["start"] for s in spans
            if s["name"] == name and start_from <= s["start"] < start_before]


def instruction_seconds(path: str, window) -> tuple:
    """(program, seconds by instruction, seconds in all) for the program that took
    most device time in ``window`` ((start_s, end_s) on the trace's clock): the
    device time of every ``XLA Ops`` event that begins inside one of that
    program's ``XLA Modules`` runs, summed over the devices, by the instruction's
    name (``fusion.26``). Raises where the file holds no device plane."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    w0, w1 = window[0] * 1e9, window[1] * 1e9
    runs_by_program: dict = {}
    ops_by_device = []
    for plane in data.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    if w0 <= e.start_ns and e.start_ns + e.duration_ns <= w1:
                        name = re.sub(r"\(\d+\)$", "", e.name)
                        runs_by_program.setdefault(name, []).append(
                            (len(ops_by_device), e.start_ns, e.start_ns + e.duration_ns))
            elif line.name == "XLA Ops":
                ops = sorted((e.start_ns, e.duration_ns, e.name) for e in line.events)
        ops_by_device.append(ops)
    if not ops_by_device:
        raise ValueError(f"{path}: no device plane")
    if not runs_by_program:
        return None, {}, 0.0
    program = max(runs_by_program, key=lambda n: sum(b - a for _, a, b in runs_by_program[n]))
    seconds: dict = {}
    starts = [[op[0] for op in ops] for ops in ops_by_device]
    for device, a, b in runs_by_program[program]:
        first, last = bisect.bisect_left(starts[device], a), bisect.bisect_left(starts[device], b)
        for _, duration, name in ops_by_device[device][first:last]:
            m = _INSTRUCTION.match(name)
            key = m.group(1) if m else name
            seconds[key] = seconds.get(key, 0.0) + duration * 1e-9
    return program, seconds, sum(seconds.values())


def device_blocks(seconds_by_instruction: dict, scopes: dict, levels: int = 2) -> list:
    """[block, seconds] for every block, costliest first: the device time of the
    instructions that ``scopes`` (the compile log's instruction-to-scope map of the
    program) places under it, a block being the first ``levels`` components of
    the module path (``InceptionV3/InceptionBlockA_0``). Together they hold the
    named device time: the whole times ``named_share``."""
    blocks: dict = {}
    for instruction, seconds in seconds_by_instruction.items():
        scope = scopes.get(instruction)
        if scope:
            block = "/".join(scope.split("/")[:levels])
            blocks[block] = blocks.get(block, 0.0) + seconds
    return [[k, v] for k, v in sorted(blocks.items(), key=lambda kv: -kv[1])]


def named_share(seconds_by_instruction: dict, scopes: dict) -> float | None:
    """Percentage of the device time whose instruction has a scope in the map."""
    total = sum(seconds_by_instruction.values())
    if total <= 0:
        return None
    named = sum(s for i, s in seconds_by_instruction.items() if scopes.get(i))
    return 100.0 * named / total


def slowest_pass_spans(spans, passes, factor: float = 1.3, n: int = 3) -> list:
    """The stall's signature: where the slowest pass took more than ``factor``
    times the median pass, one line for each of its ``n`` longest program spans
    with its parents; nothing otherwise. ``passes`` are (start_s, end_s) on the
    trace's clock."""
    if len(passes) < 3:
        return []
    lengths = [b - a for a, b in passes]
    slow = max(range(len(passes)), key=lengths.__getitem__)
    median = statistics.median(lengths)
    if lengths[slow] <= factor * median:
        return []
    a, b = passes[slow]
    by_id = {s["id"]: s for s in spans}
    inside = sorted((s for s in spans if a <= s["start"] < b),
                    key=lambda s: s["start"] - s["end"])[:n]
    lines = [f"slow pass {slow}: {lengths[slow]:.4f} s against a median of {median:.4f} s"]
    for s in inside:
        chain, parent = [], by_id.get(s["parent"])
        while parent is not None:
            chain.append(parent["name"])
            parent = by_id.get(parent["parent"])
        lines.append(f"  {s['name']} {s['end'] - s['start']:.4f} s at {s['start'] - a:.4f} s"
                     f" of the pass, under {' < '.join(chain) or 'nothing'}")
    return lines
