"""Reader ``trace_step_ms``: the median device time of one run of the compiled
program that took most of the traced window, in milliseconds."""

import statistics


def read(view: dict, params: dict):
    steps = view["trace"].step_durations() if view["trace"] else []
    return statistics.median(steps) * 1e3 if steps else None
