"""Mean device time of one operation matching ``pattern``, ms."""


def read(metric, run):
    seconds, count = run["trace"].matching(metric["pattern"],
                                           metric.get("line", "ops"))
    if not count:
        return None
    return 1e3 * seconds / count
