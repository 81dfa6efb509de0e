"""Share of the wall window outside a clock the driver kept (``fact``
names it, in seconds), %."""


def read(metric, run):
    start, end = run["window"]
    inside = run["facts"].get(metric["fact"])
    if inside is None or end <= start:
        return None
    return 100.0 * (1.0 - inside / (end - start))
