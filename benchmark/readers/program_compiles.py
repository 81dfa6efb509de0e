"""Compiles of any jitted program that ended inside the window, a count,
from the program's own record of its compile events
(``ray_tpu.observability.device_programs.compiles``, on the harness's
clock). A warmed-up run reads 0; each one is named on a commentary line.
Nothing where the program keeps no such record, and nothing where the
trace shows no device (a rehearsal on the CPU), like the trace's readers."""

from benchmark.readers._registry import device_programs


def read(metric, run):
    registry = device_programs()
    if registry is None or not run["trace"].devices:
        return None
    inside = registry.compiles(*run["window"])
    for event in inside:
        print(f"[reader] {metric['name']}: {event.program} compiled inside "
              f"the window in {event.seconds:.3f} s (cache {event.cache})",
              flush=True)
    return float(len(inside))
