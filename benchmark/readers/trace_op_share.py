"""Device time of the operations matching ``pattern`` as a share of the
time in which any operation ran, %. Nothing where no such operation ran."""


def read(metric, run):
    trace = run["trace"]
    seconds, count = trace.matching(metric["pattern"], metric.get("line", "ops"))
    busy = trace.busy_s()
    if not count or busy <= 0:
        return None
    return 100.0 * seconds / busy
