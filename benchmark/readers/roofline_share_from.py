"""A kernel's share of its roofline, %, as ``readers/roofline_share.py``
reckons it, with one call's operations and bytes taken from the module
the metric's file names: ``module`` under ``benchmark/``, ``cost`` the
function of it. Nothing where the kernel did not run."""

import importlib

from benchmark.flops import least_seconds
from benchmark.readers._lookup import resolve


def read(metric, run):
    seconds, count = run["trace"].matching(metric["pattern"],
                                           metric.get("line", "ops"))
    if not count or seconds <= 0:
        return None
    counts = importlib.import_module("benchmark." + metric["module"])
    args = {k: resolve(v, run) for k, v in metric["args"].items()}
    ops, nbytes = getattr(counts, metric["cost"])(**args)
    least, bound = least_seconds(
        ops, nbytes, run["peak"], metric.get("peak", "bf16_flops_per_s"))
    print(f"[reader] {metric['name']}: {count:g} calls a device, "
          f"{1e3 * seconds / count:.4f} ms each, least {1e3 * least:.4f} ms "
          f"({bound}-bound)", flush=True)
    return 100.0 * least * count / seconds
