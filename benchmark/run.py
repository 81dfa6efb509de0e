"""The benchmark's entry: one cell, one run, one line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

from the root of a checkout. One process, which imports JAX itself and
refuses to start unless JAX finds a TPU and exactly the chips the cell
asks for. It sets no switch of the program, starts no child that needs
the chip, and takes no notice of the environment beyond
``JAX_COMPILATION_CACHE_DIR`` (where that is set JAX keeps its compile
cache there; otherwise in the fixed ``.jax_compile_cache/`` of the
checkout).

Everything that belongs to one cell is found by name (benchmark/loader.py).
The cell's driver does the set-up and the measured window and returns what
it counted; with ``--trace 1`` the window runs under the JAX profiler and
the per-layer metrics are read from the trace, the harness's spans and the
program's counters by the readers under ``benchmark/readers``. Then the
peak memory is read, and only then does the driver's ``check`` run the
plain reference. The last line of standard output is the result; every
other line (stderr too) is commentary.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from benchmark import loader  # noqa: E402
from benchmark.spans import Spans  # noqa: E402

CACHE_DIR_NAME = ".jax_compile_cache"


def say(message: str) -> None:
    print(message, flush=True)


def require_chips(chips: int):
    """The devices, or no run: a benchmark number comes from the chip."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: JAX found no accelerator (jax.devices()[0] is "
                 f"{devices[0].platform} {devices[0].device_kind}); "
                 f"nothing was run")
    if len(devices) != chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), "
                 f"jax.devices() has {len(devices)}; nothing was run")
    return devices


def place_compile_cache() -> str:
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(loader.ROOT, CACHE_DIR_NAME)
        jax.config.update("jax_compilation_cache_dir", placed)
    # small programs too: a second run has to find every program
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


class CacheEvents:
    """Counts the persistent compilation cache's hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Context:
    """What a driver is given."""

    def __init__(self, cell: loader.Cell, seed: int, seconds: float,
                 trace: bool):
        self.cell, self.seed, self.trace = cell, seed, trace
        self.spans = Spans()
        self.say = say
        # a traced run measures a short window of its own
        self.window_seconds = (
            min(seconds, cell.workload.get("trace_seconds", seconds))
            if trace else seconds)
        self.setup_start = time.perf_counter()
        self.setup_s = None
        self.trace_dir = None

    @contextlib.contextmanager
    def window(self):
        """Around the measured window: set-up ends here; a traced run's
        profiler runs from here to the window's end."""
        import jax

        self.setup_s = time.perf_counter() - self.setup_start
        if not self.trace:
            yield
            return
        self.trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_")
        jax.profiler.start_trace(self.trace_dir)
        self.spans.annotate = True
        try:
            with jax.profiler.TraceAnnotation("bench:window"):
                yield
        finally:
            self.spans.annotate = False
            jax.profiler.stop_trace()


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def per_layer_metrics(cell, run) -> dict:
    out = {}
    for metric in cell.per_layer:
        value = loader.plugin("readers", metric["reader"]).read(metric, run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = loader.Cell(args.workload)
    devices = require_chips(cell.chips)
    cache_dir = place_compile_cache()
    cache = CacheEvents()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peak = loader.peaks(devices[0].device_kind)
    # set-up is clocked from here (Context): the interpreter, the import of
    # JAX and the TPU runtime's start-up before this line took 10 to 16 s
    # from run to run on one machine, which nothing in the repo can move
    say(f"benchmark: {cell.name} seed {args.seed} on {device}, "
        f"compile cache at {cache_dir}; JAX and its devices were up "
        f"{time.perf_counter() - PROCESS_START:.1f} s after the process "
        f"started, not counted in setup_s")

    ctx = Context(cell, args.seed, args.seconds, bool(args.trace))
    outcome = cell.driver().run(ctx)
    device["memory_peak_bytes"] = memory_peak_bytes(devices)
    say(f"benchmark: set-up {ctx.setup_s:.2f} s, compile cache hits "
        f"{cache.hits}, misses {cache.misses}; peak_bytes_in_use "
        f"{device['memory_peak_bytes']}")

    end_to_end = dict(outcome["end_to_end"], setup_s=ctx.setup_s)
    result = {"correct": False, "attempted": outcome["attempted"],
              "failed": outcome["failed"]}
    if args.trace:
        from benchmark.trace import Trace, find_xplane

        t0 = time.perf_counter()
        trace = Trace(find_xplane(ctx.trace_dir))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        run = {"trace": trace, "spans": ctx.spans, "peak": peak,
               "chips": cell.chips, "config": cell.config,
               "workload": cell.workload, "window": outcome["window"],
               "facts": outcome["facts"], "end_to_end": end_to_end,
               "counters": outcome.get("counters", {})}
        result["metrics"] = per_layer_metrics(cell, run)
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.idle_gaps()}
        say(f"benchmark: trace read in {time.perf_counter() - t0:.1f} s")
    else:
        reported = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {name: {"value": end_to_end[name], "unit": unit}
                             for name, unit in reported.items()}
    result["device"] = device

    t0 = time.perf_counter()
    correct, compared = outcome["check"]()
    say(f"benchmark: comparison took {time.perf_counter() - t0:.1f} s")
    result["correct"] = bool(correct) and outcome["failed"] == 0
    result["compared"] = compared  # last in the line
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
