"""Reader ``trace_step_mfu``: the step's share of the chip's bf16 peak. The FLOPs
are the benchmark's own count for the rows one device's step carries (real rows,
not padding: ``observed["rows_per_device_step"]``), over the median device time of
the step, over the published peak of the device kind."""

import statistics


def read(view: dict, params: dict):
    steps = view["trace"].step_durations() if view["trace"] else []
    rows = view["observed"].get("rows_per_device_step")
    if not steps or not rows or view["peaks"] is None:
        return None
    flops = view["flops_per_row"] * rows
    return 100.0 * flops / statistics.median(steps) / view["peaks"]["bf16_flops_per_s"]
