"""Reader ``trace_kernel_share``: of the device time of the whole runs of the program
that took most of the trace, the percentage spent in one kernel. ``params``: ``kernel``
is the name the kernel was given in the program: a ``pallas_call``'s name, or a
``jax.named_scope`` that the compiled instructions keep in their ``op_name``. The
driver hands over the program's instruction-to-scope map (``observed["program.scopes"]``,
from the compile log); an ``XLA Ops`` event belongs to the kernel where its instruction's
scope path has the name as a component. No map, no trace or no device plane: nothing
returned."""

from __future__ import annotations

import bisect
import re

from benchmarks import harness, tracing

_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def _program_seconds(view: dict):
    """``(seconds by instruction, steps, seconds of those steps)`` over every whole run
    of the dominant program in the trace file, summed over the devices; None with no
    trace to read. Read once a run: the view keeps it for the other kernels' metrics."""
    if "_program_seconds" in view:
        return view["_program_seconds"]
    view["_program_seconds"] = None
    try:
        path = tracing.find_trace_file(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    import jax
    runs: dict = {}
    ops_by_device = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not tracing._DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    runs.setdefault(re.sub(r"\(\d+\)$", "", e.name), []).append(
                        (len(ops_by_device), e.start_ns, e.start_ns + e.duration_ns))
            elif line.name == "XLA Ops":
                ops = sorted((e.start_ns, e.duration_ns, e.name) for e in line.events)
        ops_by_device.append(ops)
    if not runs:
        return None
    program = max(runs.values(), key=lambda rs: sum(b - a for _, a, b in rs))
    seconds: dict = {}
    for device, a, b in program:
        starts = [op[0] for op in ops_by_device[device]]
        for _, duration, name in ops_by_device[device][bisect.bisect_left(starts, a):
                                                       bisect.bisect_left(starts, b)]:
            m = _INSTRUCTION.match(name)
            if m:
                seconds[m.group(1)] = seconds.get(m.group(1), 0.0) + duration * 1e-9
    view["_program_seconds"] = (seconds, len(program),
                                sum(b - a for _, a, b in program) * 1e-9)
    return view["_program_seconds"]


def kernel_seconds(view: dict, kernel: str):
    """``(kernel seconds, steps, seconds of those steps)``; None with nothing to read."""
    scopes = view["observed"].get("program.scopes")
    if not scopes or view.get("trace") is None:
        return None
    found = _program_seconds(view)
    if found is None:
        return None
    seconds, steps, step_seconds = found
    mine = sum(s for name, s in seconds.items()
               if kernel in scopes.get(name, "").split("/"))
    return mine, steps, step_seconds


def read(view: dict, params: dict):
    found = kernel_seconds(view, params["kernel"])
    if found is None or found[2] <= 0:
        return None
    return 100.0 * found[0] / found[2]
