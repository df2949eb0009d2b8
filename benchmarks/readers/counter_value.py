"""Reader ``counter_value``: one of the driver's ``observed`` readings, scaled.
``params``: ``key``, ``scale`` (default 1). Nothing to read: nothing returned."""


def read(view: dict, params: dict):
    value = view["observed"].get(params["key"])
    return None if value is None else value * params.get("scale", 1.0)
