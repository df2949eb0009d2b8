"""From a profiler trace to device busy time, step time, the costliest operations
and the longest idle gaps, each gap named by what the host was doing.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX alone. Device planes
are named ``/device:TPU:<n>``; their line ``XLA Ops`` holds one event per operation
and ``XLA Modules`` one per run of a compiled program. The traced window and the
drivers' spans come from ``harness.Tracer``, on the trace's clock: the host tracer
is off in every run, so the trace itself holds no span."""

from __future__ import annotations

import glob
import os
import re
import statistics
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class DeviceTrace:
    busy_s: float
    modules: dict  # program name -> list of durations (s) of its runs
    ops: dict  # operation name -> total seconds
    gaps: list  # (start_s, end_s) idle intervals inside the window, window-relative


@dataclass
class TraceSummary:
    window_s: float
    devices: dict = field(default_factory=dict)  # ordinal -> DeviceTrace
    spans: list = field(default_factory=list)  # (name, start_s, end_s), window-relative

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices traced."""
        return statistics.fmean(d.busy_s for d in self.devices.values())

    def step_durations(self) -> list:
        """Durations of the runs of the program that took most device time."""
        merged: dict = {}
        for dev in self.devices.values():
            for name, runs in dev.modules.items():
                merged.setdefault(name, []).extend(runs)
        if not merged:
            return []
        return max(merged.values(), key=sum)

    def top_ops(self, n: int = 10) -> list:
        total: dict = {}
        for dev in self.devices.values():
            for name, seconds in dev.ops.items():
                total[name] = total.get(name, 0.0) + seconds / len(self.devices)
        return [[short_op_name(k), v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the idlest device, each named by ``label_gap``."""
        dev = min(self.devices.values(), key=lambda d: d.busy_s)
        longest = sorted(dev.gaps, key=lambda g: g[0] - g[1])[:n]
        return [[self.label_gap(a, b), b - a] for a, b in longest]

    def label_gap(self, start_s: float, end_s: float) -> str:
        """A gap that reaches the window's edge is named for it; one in which a pass
        or a partition begins is named for that boundary; any other lies within a
        partition, between two device batches."""
        if start_s <= 0.0:
            return "trace_start"
        if end_s >= self.window_s:
            return "trace_end"
        begun = {name for name, s, _ in self.spans if start_s <= s <= end_s}
        for span, name in (("bench.pass", "at_pass_boundary"),
                           ("bench.partition", "at_partition_boundary")):
            if span in begun:
                return name
        return "within_partition"


def short_op_name(name: str) -> str:
    """``%fusion.26 = bf16[1024,71,71,192]{...} fusion(...)`` -> ``fusion.26_bf16_1024_71_71_192``."""
    m = re.match(r"^%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]", name)
    if not m:
        return name[:60]
    return f"{m.group(1)}_{m.group(2)}_{m.group(3).replace(',', '_')}"[:60]


def find_trace_file(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce_trace(path: str, window, spans) -> TraceSummary:
    """Everything the readers take from one trace file. ``window`` is (start_s,
    end_s) and ``spans`` are (name, start_s, end_s), in seconds from the trace's
    zero, as ``harness.Tracer`` keeps them. Raises where there is no device plane."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(n, a * 1e9, b * 1e9) for n, a, b in spans]
    window = (window[0] * 1e9, window[1] * 1e9)
    device_planes = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            device_planes[int(m.group(1))] = plane
    if not device_planes:
        raise ValueError(f"{path}: no device plane")
    w0, w1 = window
    to_s = lambda ns: (ns - w0) * 1e-9
    summary = TraceSummary(window_s=to_s(w1))
    summary.spans = sorted(((n, to_s(a), to_s(b)) for n, a, b in spans if b > w0 and a < w1),
                           key=lambda s: s[1])
    for ordinal, plane in sorted(device_planes.items()):
        intervals, ops, modules = [], {}, {}
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
                    if b > a:
                        intervals.append((a, b))
                        ops[e.name] = ops.get(e.name, 0.0) + (b - a) * 1e-9
            elif line.name == "XLA Modules":
                for e in line.events:
                    if w0 <= e.start_ns and e.start_ns + e.duration_ns <= w1:
                        name = re.sub(r"\(\d+\)$", "", e.name)
                        modules.setdefault(name, []).append(e.duration_ns * 1e-9)
        busy = _union(intervals)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps = [(to_s(edges[i]), to_s(edges[i + 1])) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        summary.devices[ordinal] = DeviceTrace(
            busy_s=sum(b - a for a, b in busy) * 1e-9, modules=modules, ops=ops, gaps=gaps)
    return summary
