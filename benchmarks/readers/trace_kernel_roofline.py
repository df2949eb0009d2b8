"""Reader ``trace_kernel_roofline``: a kernel's share of its roofline. The least time the
chip could take for the kernel's work in one step, the larger of its operations over the
chip's bf16 peak and its bytes over the peak memory bandwidth, over the kernel's device
time a step in the trace (``trace_kernel_share.kernel_seconds``), as a percentage.
``params``: ``kernel`` is the kernel's name in the program (a ``pallas_call``'s name or a
named scope), ``work`` the function that counts its operations and bytes for one step from
the configuration's shapes alone, as ``<module>.<function>`` under ``benchmarks/``
(``kernel_work.attention``). With no trace, no scope map or no such kernel in the program
(a program from before the kernel existed): nothing returned."""

from __future__ import annotations

import importlib

from benchmarks.readers.trace_kernel_share import kernel_seconds


def read(view: dict, params: dict):
    found = kernel_seconds(view, params["kernel"])
    rows = view["observed"].get("rows_per_device_step")
    tokens = view["observed"].get("tokens_per_row")
    if found is None or not found[0] or not rows or not tokens or view["peaks"] is None:
        return None
    seconds, steps, _ = found
    module, function = params["work"].rsplit(".", 1)
    work = getattr(importlib.import_module(f"benchmarks.{module}"), function)(
        view["config"], rows, tokens)
    least = max(work["flops"] / view["peaks"]["bf16_flops_per_s"],
                work["bytes"] / view["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
