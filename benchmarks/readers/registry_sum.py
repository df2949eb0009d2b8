"""Reader ``registry_sum``: counters of the program's own metrics registry
(``sparkdl_tpu.obs.default_registry().snapshot()``), which are always on: no tracer
and no driver has to hand them over, so the reader reaches past ``view`` for them, as
``trace_kernel_share`` does for the trace file. ``params``: ``counters`` (names; their
sum), ``scale`` (default 1), and optionally ``per`` (names; the first sum is divided by
this one's) and ``complement`` (true: one minus that ratio, before the scale). The
readers run once the comparer has, so a counter holds the whole process: set-up, the
window and the reference; the ones read here count the program's instrumented functions
alone. A named counter that was never made (a program from before it), or a ``per`` that
sums to nothing: nothing returned."""


def _sum(snapshot: dict, names):
    if any(name not in snapshot for name in names):
        return None
    return float(sum(snapshot[name] for name in names))


def read(view: dict, params: dict):
    from sparkdl_tpu.obs import default_registry
    snapshot = default_registry().snapshot()
    value = _sum(snapshot, params["counters"])
    if value is None:
        return None
    if "per" in params:
        per = _sum(snapshot, params["per"])
        if not per:
            return None
        value /= per
        if params.get("complement"):
            value = 1.0 - value
    return value * params.get("scale", 1.0)
