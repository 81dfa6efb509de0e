"""A counter of the program over the window (the driver reads it before
and after), divided by a fact of the run (``per``) where one is named."""


def read(metric, run):
    value = run["counters"].get(metric["counter"])
    if value is None:
        return None
    if "per" in metric:
        per = run["facts"].get(metric["per"])
        if not per:
            return None
        value = value / per
    return float(value)
