"""Reader ``counter_ratio``: one counter over another, as a percentage.
``params``: ``numerator``, ``denominator`` (keys of the driver's ``observed``),
``complement`` (true: 100 minus the share). Nothing to read: nothing returned."""


def read(view: dict, params: dict):
    observed = view["observed"]
    num, den = observed.get(params["numerator"]), observed.get(params["denominator"])
    if num is None or not den:
        return None
    share = 100.0 * num / den
    return 100.0 - share if params.get("complement") else share
