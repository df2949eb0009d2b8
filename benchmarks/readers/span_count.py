"""Reader ``span_count``: how many of the program's spans of one name begin from the
trace's zero on: the run's window, whose first pass starts where the profiler does.
``params``: ``span`` is the name. No program spans in the view: nothing returned; none
of that name: 0."""

from benchmarks import boundary


def read(view: dict, params: dict):
    program = view.get("program")
    if not program:
        return None
    return len(boundary.span_lengths(program["spans"], params["span"], start_from=0.0))
