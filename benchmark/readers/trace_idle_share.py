"""Share of the traced window in which no operation ran on the device, %."""


def read(metric, run):
    trace = run["trace"]
    if trace.window_s <= 0 or not trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
