"""How a cell of the ``hybrid_train_steps`` driver routes over a window,
for each of a few rates of the correction bias' update: the evidence for
the configuration's ``run.router_bias_rate``.

For every rate the cell's own driver, set-up and window (``--seconds``),
from the same seed, with that rate put in the configuration's place: the driver's lines say
the rows the held experts drew in every step beside the rows expected
and the row buffer. One JSON line a rate (tokens/s of the window, steps,
failed, the facts), to standard output and
``chiprun_out/routing.<cell>.jsonl``.

    python3 -m benchmark.tools.routing_hybrid --workload W --seed N
        --rates 0,0.01,0.03 [--seconds 20]
"""

import argparse
import json
import os

from benchmark import loader
from benchmark import run as harness


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    args = parser.parse_args()
    cell = loader.Cell(args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    driver = cell.driver()
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/routing.{cell.name}.jsonl", "a") as out:
        for rate in (float(r) for r in args.rates.split(",")):
            cell.config["run"]["router_bias_rate"] = rate
            ctx = harness.Context(cell, args.seed, args.seconds, False)
            outcome = driver.run(ctx)
            line = json.dumps(dict(
                rate=rate, seed=args.seed, failed=outcome["failed"],
                attempted=outcome["attempted"], facts=outcome["facts"],
                **outcome["end_to_end"]))
            print(line, flush=True)
            out.write(line + "\n")


if __name__ == "__main__":
    main()
