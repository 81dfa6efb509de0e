"""A kernel's share of its roofline, %: the least time the chip could
take for the calls seen in the trace (``cost`` names the function of
benchmark/flops.py that gives one call's operations and bytes from shapes;
the larger of operations over peak and bytes over peak bandwidth) over the
device time those calls took. Nothing where the kernel did not run."""

from benchmark import flops
from benchmark.readers._lookup import resolve


def read(metric, run):
    seconds, count = run["trace"].matching(metric["pattern"],
                                           metric.get("line", "ops"))
    if not count or seconds <= 0:
        return None
    args = {k: resolve(v, run) for k, v in metric["args"].items()}
    ops, nbytes = getattr(flops, metric["cost"])(**args)
    least, bound = flops.least_seconds(
        ops, nbytes, run["peak"], metric.get("peak", "bf16_flops_per_s"))
    print(f"[reader] {metric['name']}: {count:g} calls a device, "
          f"{1e3 * seconds / count:.4f} ms each, least {1e3 * least:.4f} ms "
          f"({bound}-bound)", flush=True)
    return 100.0 * least * count / seconds
