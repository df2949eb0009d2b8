"""Reader ``trace_named_share``: of the device time of the ``XLA Ops`` events inside
runs of the program that took most of the traced window, the percentage whose
instruction has a module path in the compile log's scope map of that program. No
program spans in the view, no device plane, or no map of that program (it was
compiled while the compile log was disarmed): nothing returned."""

from benchmarks import boundary


def read(view: dict, params: dict):
    device = (view.get("program") or {}).get("device")
    if not device or not device["scopes"]:
        return None
    return boundary.named_share(device["seconds_by_instruction"], device["scopes"])
