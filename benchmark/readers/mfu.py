"""The whole step's share of the chips' peak, %: operations the forward
and backward passes need per token (``flops`` names the function of
benchmark/flops.py; recomputation not counted) times the tokens per second
of this run's window, over chips times the peak."""

from benchmark import flops


def read(metric, run):
    rate = run["end_to_end"].get("tokens_per_s")
    if not rate:
        return None
    per_token = getattr(flops, metric["flops"])(run["config"],
                                                run["facts"]["seq"])
    return 100.0 * per_token * rate / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
