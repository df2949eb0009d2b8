"""Reader ``trace_idle_share``: 100 minus the share of the traced window in which an
operation ran on the device. ``params``: ``which`` is ``mean`` over the devices
(default) or ``max``, the idlest device."""


def read(view: dict, params: dict):
    trace = view["trace"]
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    shares = [100.0 * (1.0 - d.busy_s / trace.window_s) for d in trace.devices.values()]
    return max(shares) if params.get("which") == "max" else sum(shares) / len(shares)
