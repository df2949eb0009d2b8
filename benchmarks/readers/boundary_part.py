"""Reader ``boundary_part``: the median, in milliseconds, of one part of the idlest
device's partition-boundary gaps (``boundary.boundary_gaps``). ``params``: ``part`` is
``gap``, ``drain_tail``, ``handoff``, ``refill_host`` or ``first_step_lag``. No program
spans in the view, no device plane or no boundary in the window: nothing returned."""

import statistics


def read(view: dict, params: dict):
    boundaries = (view.get("program") or {}).get("boundaries")
    if not boundaries:
        return None
    return 1e3 * statistics.median(g[params["part"]] for g in boundaries)
