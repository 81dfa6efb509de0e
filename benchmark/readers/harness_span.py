"""The harness's own spans of one name inside the window: as a share of
the window (``as: share``, %) or as milliseconds a span (``as: ms``)."""


def read(metric, run):
    start, end = run["window"]
    seconds, count = run["spans"].total(metric["span"], start, end)
    if not count or end <= start:
        return None
    if metric.get("as", "share") == "ms":
        return 1e3 * seconds / count
    return 100.0 * seconds / (end - start)
