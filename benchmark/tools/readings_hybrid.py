"""``benchmark.tools.readings`` for a cell of the ``hybrid_train_steps``
driver: the readings its limits are set from, in one process on the chip
at the cell's own size, each judged by the cell's own limits.

For every seed of ``--seeds`` the program's first two steps (the
driver's own set-up, no measured window) against the plain reference:
the lower readings, which have to be judged correct. For every seed of
``--controls`` the reference alone, and against it the control (the
reference with the operand rule ``check.control`` names) and each planted
fault: those ``check.faults`` names (the reference's own: the carried
state zeroed at every chunk boundary, the routed experts left out) and
``half_batch`` (the reference on the first half of each batch's rows),
each of which has to be judged not correct. One JSON line a reading,
with ``correct`` and the numbers over their limits (``refused_by``), to
standard output and ``chiprun_out/readings.<cell>.jsonl``.

    python3 -m benchmark.tools.readings_hybrid --workload W
        [--seeds 1,2,3] [--controls 4,5,6] [--faults 0]
"""

import argparse
import json
import os
import time

from benchmark import compare, loader
from benchmark import run as harness


def seeds_of(text: str):
    return [int(s) for s in text.split(",") if s]


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--controls", default="")
    parser.add_argument("--faults", type=int, default=1,
                        help="0: on --controls the control alone")
    args = parser.parse_args()
    cell = loader.Cell(args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    driver = cell.driver()
    spec = cell.workload["check"]
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(f"chiprun_out/readings.{cell.name}.jsonl", "a")

    def emit(seed, what, got, ref, **more):
        numbers = compare.training_numbers(got, ref)
        correct, compared = compare.judge(numbers, spec["limits"])
        reading = dict(
            numbers, seed=seed, what=what, correct=correct,
            refused_by=[name for name, c in compared.items()
                        if c["value"] > c["limit"]],
            first_grad_gaps=compare.leaf_gaps(got["first_grad"],
                                              ref["first_grad"]), **more)
        line = json.dumps(reading)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    for seed in seeds_of(args.seeds):
        ctx = harness.Context(cell, seed, 0.0, False)
        said = []
        ctx.say = lambda message: (said.append(message),
                                   harness.say(message))
        t0 = time.perf_counter()
        outcome = driver.run(ctx)
        t1 = time.perf_counter()
        ref = driver.follow(ctx)
        emit(seed, "program", outcome["program_numbers"], ref,
             seconds=[t1 - t0, time.perf_counter() - t1],
             failed=outcome["failed"],
             rows=[m for m in said if "expert rows" in m])
    for seed in seeds_of(args.controls):
        ctx = harness.Context(cell, seed, 0.0, False)
        ref = driver.follow(ctx)
        emit(seed, "control:" + spec["control"],
             driver.follow(ctx, operand=spec["control"]), ref)
        if not args.faults:
            continue
        for fault in spec["faults"]:
            emit(seed, "fault:" + fault, driver.follow(ctx, fault=fault), ref)
        emit(seed, "fault:half_batch",
             driver.follow(ctx, rows=cell.workload["batch"] // 2), ref)


if __name__ == "__main__":
    main()
