"""One fact of the run over another, times ``scale``."""


def read(metric, run):
    top = run["facts"].get(metric["numerator"])
    bottom = run["facts"].get(metric["denominator"])
    if top is None or not bottom:
        return None
    return metric.get("scale", 1.0) * top / bottom
