"""Several runs of one cell, one after another, each a process of its own
(this one never touches JAX, so the chip is free for each). Every run's
whole output goes to ``chiprun_out/runs/<tag>.<seed>.log``; what is
printed is each run's commentary of the harness, its result line, and at
the end each metric's median and spread (distance of the quartiles over
the median, as ``statistics.quantiles(values, n=4)`` gives them).

    python3 -m benchmark.tools.several --workload W --seeds 1,2,3
        [--seconds S] [--trace 0|1] [--tag T]
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", default=None)
    parser.add_argument("--trace", default="0")
    parser.add_argument("--tag", default="run")
    args = parser.parse_args()
    spec = json.load(open("BENCHMARK.json"))
    seconds = args.seconds or str(spec["run_seconds"])
    os.makedirs("chiprun_out/runs", exist_ok=True)
    values = {}
    for seed in args.seeds.split(","):
        cmd = spec["command"] + ["--workload", args.workload, "--seed", seed,
                                 "--seconds", seconds, "--trace", args.trace]
        t0 = time.time()
        done = subprocess.run(cmd, capture_output=True, text=True)
        wall = time.time() - t0
        log = f"chiprun_out/runs/{args.tag}.{seed}.log"
        with open(log, "w") as f:
            f.write(done.stdout + "\n--- stderr ---\n" + done.stderr)
        lines = done.stdout.strip().splitlines()
        for line in lines[:-1]:
            if line.startswith(("benchmark:", "[train]", "[sched]",
                                "[reader]")):
                print("   ", line[:400])
        print(f"seed {seed}: exit {done.returncode} in {wall:.1f} s: "
              f"{lines[-1] if lines else done.stderr[-2000:]}", flush=True)
        if done.returncode == 0:
            result = json.loads(lines[-1])
            for name, m in result["metrics"].items():
                values.setdefault(name, []).append(m["value"])
            values.setdefault("correct", []).append(result["correct"])
    for name, vs in values.items():
        if name == "correct":
            print(f"correct: {sum(vs)} of {len(vs)}")
        elif len(vs) >= 2:
            q = statistics.quantiles(vs, n=4)
            med = statistics.median(vs)
            print(f"{name}: median {med!r} spread {(q[2] - q[0]) / med:.5f} "
                  f"values {vs}")


if __name__ == "__main__":
    sys.exit(main())
