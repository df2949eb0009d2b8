"""Reader ``trace_kernel_roofline``: a kernel's share of its roofline. The least time the
chip could take for the kernel's work in one step, the larger of its operations over the
chip's bf16 peak and its bytes over the peak memory bandwidth (``kernel_work.py``: from
the configuration's shapes alone), over the kernel's device time a step in the trace
(``trace_kernel_share.kernel_seconds``), as a percentage. ``params``: ``kernel`` is the
kernel's name in the program and in ``kernel_work.KERNELS``."""

from __future__ import annotations

from benchmarks import kernel_work
from benchmarks.readers.trace_kernel_share import kernel_seconds


def read(view: dict, params: dict):
    found = kernel_seconds(view, params["kernel"])
    rows = view["observed"].get("rows_per_device_step")
    tokens = view["observed"].get("tokens_per_row")
    if found is None or not found[0] or not rows or not tokens or view["peaks"] is None:
        return None
    seconds, steps, _ = found
    work = kernel_work.KERNELS[params["kernel"]](view["config"], rows, tokens)
    least = max(work["flops"] / view["peaks"]["bf16_flops_per_s"],
                work["bytes"] / view["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
