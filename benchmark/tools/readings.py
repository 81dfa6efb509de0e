"""The readings a training cell's limits are set from, in one process on
the chip at the cell's own size.

For every seed: the program's first two steps (through the driver's own
set-up; no measured window is needed) against the plain reference: the
lower readings. For the first ``--controls`` seeds also the control (the
reference with the operand rule the cell's ``check.control`` names, put in
the program's place) and the planted fault "half of the batch left out,
the mean taken over the rest" (the reference on the first half of each
batch), each against the same reference. One JSON line a reading, to
standard output and ``chiprun_out/readings.<cell>.jsonl``.

    python3 -m benchmark.tools.readings --workload W --seeds 1,2,3
        [--controls 3]
"""

import argparse
import json
import os
import time

from benchmark import compare, loader
from benchmark import run as harness


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--program", type=int, default=1,
                        help="0: the controls and faults alone")
    args = parser.parse_args()
    cell = loader.Cell(args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    driver = cell.driver()
    spec = cell.workload["check"]
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(f"chiprun_out/readings.{cell.name}.jsonl", "a")

    def numbers(got, ref):
        """The numbers compared, and for a look at their spread every
        leaf's gap of the first gradient and the whole gradient's norm."""
        whole = [float(sum(float((v ** 2).sum())
                           for v in x["first_grad"].values()) ** 0.5)
                 for x in (got, ref)]
        return dict(compare.training_numbers(got, ref),
                    whole_grad_gap=abs(whole[0] - whole[1]) / whole[1],
                    first_grad_gaps=compare.leaf_gaps(
                        got["first_grad"], ref["first_grad"]))

    def emit(**reading):
        line = json.dumps(reading)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(cell, seed, 0.0, False)
        t0 = time.perf_counter()
        outcome = driver.run(ctx) if args.program else None
        t1 = time.perf_counter()
        ref = driver.follow(ctx)
        t2 = time.perf_counter()
        if args.program:
            emit(seed=seed, what="program", seconds=[t1 - t0, t2 - t1],
                 **numbers(outcome["program_numbers"], ref))
        if n < args.controls:
            control = driver.follow(ctx, operand=spec["control"])
            emit(seed=seed, what="control:" + spec["control"],
                 **numbers(control, ref))
            half = driver.follow(ctx, rows=cell.workload["batch"] // 2)
            emit(seed=seed, what="fault:half_batch", **numbers(half, ref))


if __name__ == "__main__":
    main()
