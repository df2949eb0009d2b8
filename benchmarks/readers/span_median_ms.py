"""Reader ``span_median_ms``: the median length, in milliseconds, of the program's
spans of one name. ``params``: ``span`` is the name; ``after_trace`` (true) keeps the
spans that begin after the profiler stopped, the untraced passes. No program spans in
the view, or none of that name: nothing returned."""

import statistics

from benchmarks import boundary


def read(view: dict, params: dict):
    program = view.get("program")
    if not program:
        return None
    start = program["window"][1] if params.get("after_trace") else float("-inf")
    lengths = boundary.span_lengths(program["spans"], params["span"], start_from=start)
    return 1e3 * statistics.median(lengths) if lengths else None
