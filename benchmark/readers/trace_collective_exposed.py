"""Collective time during which no other operation ran on the device, as
a share of the traced window, %. Nothing where no collective ran."""


def read(metric, run):
    trace = run["trace"]
    if trace.window_s <= 0:
        return None
    total, exposed = trace.collective_exposed_s()
    if total <= 0:
        return None
    print(f"[reader] {metric['name']}: collectives {total:.4f} s a device, "
          f"{exposed:.4f} s of it exposed, window {trace.window_s:.4f} s",
          flush=True)
    return 100.0 * exposed / trace.window_s
