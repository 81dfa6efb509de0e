"""``benchmark.tools.readings_hybrid`` for a driver on
``benchmark/drivers/_expert_train_steps.py`` whose comparison has the
norm of the gradients' difference (``benchmark/compare_difference.py``):
the readings the cell's limits are set from, in one process on the chip
at the cell's own size, each judged by the cell's own limits.

For every seed the plain reference is followed once. Where the seed is
in ``--seeds`` the program's first two steps go before it (the driver's
own set-up, no measured window) and are judged against it: the lower
readings, which have to be correct. Where it is in ``--controls`` the
control follows (the reference with the operand rule ``check.control``
names), where it is in ``--fault-seeds`` each fault of ``--faults``
(those ``check.faults`` names, and ``half_batch``: the reference on the
first half of each batch's rows; default all), each set against the
float32 reference's gradient leaf by leaf: the upper readings, which
have to be not correct. One JSON line a reading, with ``correct``, the
numbers over their limits (``refused_by``) and both gradient numbers
leaf by leaf, to standard output and
``chiprun_out/readings.<cell>.jsonl``.

    python3 -m benchmark.tools.readings_expert --workload W
        [--seeds 1,2,3] [--controls 1,2] [--fault-seeds 1,2,3]
        [--faults state_reset,half_batch]
"""

import argparse
import json
import os
import time

from benchmark import compare, compare_difference, loader
from benchmark import run as harness


def seeds_of(text: str):
    return [int(s) for s in text.split(",") if s]


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--controls", default="")
    parser.add_argument("--fault-seeds", default="")
    parser.add_argument("--faults", default="")
    args = parser.parse_args()
    cell = loader.Cell(args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    driver = cell.driver()
    spec = cell.workload["check"]
    faults = ([f for f in args.faults.split(",") if f]
              or spec["faults"] + ["half_batch"])
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(f"chiprun_out/readings.{cell.name}.jsonl", "a")

    def emit(seed, what, got, ref, **more):
        numbers = compare_difference.training_numbers(got, ref)
        correct, compared = compare.judge(numbers, spec["limits"])
        reading = dict(
            numbers, seed=seed, what=what, correct=correct,
            refused_by=[name for name, c in compared.items()
                        if c["value"] > c["limit"]],
            first_grad_gaps=compare.leaf_gaps(got["first_grad"],
                                              ref["first_grad"]),
            first_grad_diffs=compare_difference.leaf_differences(
                got.get("first_grad_diff", ref.get("first_grad_diff")),
                ref["first_grad"]), **more)
        line = json.dumps(reading)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    programs, controls, faulted = (seeds_of(args.seeds),
                                   seeds_of(args.controls),
                                   seeds_of(args.fault_seeds))
    for seed in dict.fromkeys(programs + controls + faulted):
        import jax.numpy as jnp

        ctx = harness.Context(cell, seed, 0.0, False)
        more, said, t0 = {}, [], time.perf_counter()
        if seed in programs:
            ctx.say = lambda message: (said.append(message),
                                       harness.say(message))
            outcome = driver.run(ctx)
            got = outcome["program_numbers"]
            more["against"] = driver.leaf_of(
                got["first_grad_leaves"], config=cell.config,
                scale=got["first_grad_scale"])
        t1 = time.perf_counter()
        ref = driver.follow(ctx, keep=seed in controls + faulted, **more)
        if seed in programs:
            emit(seed, "program", got, ref,
                 seconds=[t1 - t0, time.perf_counter() - t1],
                 failed=outcome["failed"],
                 rows=[m for m in said if "expert rows" in m])
            del got, outcome, more
        held = ref.pop("first_grad_leaves", None)

        def against(name, entry, key):
            return jnp.asarray(held[name, entry])

        if seed in controls:
            emit(seed, "control:" + spec["control"], driver.follow(
                ctx, operand=spec["control"], against=against), ref)
        for fault in faults if seed in faulted else ():
            if fault == "half_batch":
                got = driver.follow(ctx, rows=cell.workload["batch"] // 2,
                                    against=against)
            else:
                got = driver.follow(ctx, fault=fault, against=against)
            emit(seed, "fault:" + fault, got, ref)


if __name__ == "__main__":
    main()
