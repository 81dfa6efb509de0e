"""The whole step's share of the chips' peak, %, as ``readers/mfu.py``
reckons it, with the count of operations per token taken from the module
the metric's file names: ``module`` under ``benchmark/``, ``flops`` the
function of it (recomputation not counted)."""

import importlib


def read(metric, run):
    rate = run["end_to_end"].get("tokens_per_s")
    if not rate:
        return None
    counts = importlib.import_module("benchmark." + metric["module"])
    per_token = getattr(counts, metric["flops"])(run["config"],
                                                 run["facts"]["seq"])
    return 100.0 * per_token * rate / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
