"""A named scope's share of its roofline, %: the least time the chip
could take for the work done under the scope over the device time spent
there, read by scope and pass (``readers/trace_scope_share.py``'s join of
the trace with the program's own record) and not by a kernel's name, so
that whatever implements the scope next is read on the same yardstick.

The metric's file names the ``program``, the ``scope`` and the ``pass``
(regexes, matched whole), the ``module`` under ``benchmark/`` and its
function ``cost`` that gives (operations, bytes) of one call from
``args``, and ``calls_per_step``: how many such calls one step of the
program makes in those passes. The steps are the program's events on the
trace's ``XLA Modules`` line. Nothing where the program keeps no record,
the trace shows no device, or nothing ran under the scope."""

import importlib
import re

from benchmark.flops import least_seconds
from benchmark.readers._lookup import resolve
from benchmark.readers.trace_scope_share import (
    own_by_op,
    pass_of,
    scope_table_of,
    scopes_of,
)


def read(metric, run):
    program = metric["program"]
    table = scope_table_of(run, program)
    if table is None or not run["trace"].devices:
        return None
    under = f"jit({program})"
    seconds = 0.0
    for op, s in own_by_op(run).items():
        path = table.get(op, "")
        if (path.startswith(under)
                and any(re.fullmatch(metric["scope"], x)
                        for x in scopes_of(path))
                and re.fullmatch(metric["pass"], pass_of(path))):
            seconds += s
    _, steps = run["trace"].matching(rf"^jit_{program}\b", "modules")
    calls = steps * resolve(metric["calls_per_step"], run)
    if seconds <= 0 or not calls:
        return None
    counts = importlib.import_module("benchmark." + metric["module"])
    args = {k: resolve(v, run) for k, v in metric["args"].items()}
    ops, nbytes = getattr(counts, metric["cost"])(**args)
    least, bound = least_seconds(ops, nbytes, run["peak"])
    print(f"[reader] {metric['name']}: {calls:g} calls a device in "
          f"{steps:g} steps, {1e3 * seconds / calls:.4f} ms each under "
          f"{metric['scope']} ({metric['pass']}), least {1e3 * least:.4f} "
          f"ms ({bound}-bound)", flush=True)
    return 100.0 * least * calls / seconds
