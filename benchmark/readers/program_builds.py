"""Set-up by the phases of the builds of its programs, from the
program's own record of them
(``ray_tpu.observability.device_programs.builds``, on the harness's
clock): every event that ended before the window's start. The
reference's programs are built after the window and are left out by it.

A metric file names the ``quantity``:

- ``trace``, ``lower``, ``cache_read``: the seconds of the events of that
  phase (a ``trace`` event is the outermost trace of a program; what was
  traced inside it is in its table, not in the ring);
- ``compile_missed``: the seconds of the compile events that the
  persistent cache did not answer (0 in a warm run);
- ``kernel_trace``: the seconds Pallas took to trace kernel bodies
  (``device_programs.kernel_trace``), all kernels;
- ``programs_built``: the compile events, a count;
- ``build_share``: the part of ``setup_s`` during which some trace,
  lowering or compile was open, %: the union of the events' intervals
  inside set-up, so that a program built while another is traced counts
  once and the share cannot pass 100. The rest of set-up runs programs.

``commentary`` in the file prints one line a build (the program, its
phases, the cache's answer) and the ten largest functions and kernels of
the trace of ``program``.
Nothing where the program keeps no such record, and nothing where the
trace shows no device (a rehearsal on the CPU), like the trace's readers.
"""

from benchmark.readers._registry import device_programs
from benchmark.trace import union

BUILDING = ("trace", "lower", "compile")


def seconds_of(events, phase: str) -> float:
    return sum(e.seconds for e in events if e.phase == phase)


def build_share(events, setup_s: float, setup_end: float) -> float:
    start = setup_end - setup_s
    open_s, _ = union((max(e.at - e.seconds, start), e.at) for e in events
                      if e.phase in BUILDING and e.at > start)
    return 100.0 * open_s / setup_s


QUANTITIES = {
    "trace": lambda events, run: seconds_of(events, "trace"),
    "lower": lambda events, run: seconds_of(events, "lower"),
    "cache_read": lambda events, run: seconds_of(events, "cache_read"),
    "compile_missed": lambda events, run: seconds_of(
        [e for e in events if e.cache != "hit"], "compile"),
    "kernel_trace": lambda events, run: sum(
        seconds for e in events for _, seconds, _ in e.kernels),
    "programs_built": lambda events, run: float(sum(
        e.phase == "compile" for e in events)),
    "build_share": lambda events, run: build_share(
        events, run["end_to_end"]["setup_s"], run["window"][0]),
}


def say(metric, events) -> None:
    name = metric["name"]
    builds = []  # a program's consecutive phases, oldest build first
    for e in events:
        if (not builds or builds[-1][0].program != e.program
                or e.phase in [b.phase for b in builds[-1]]):
            builds.append([])
        builds[-1].append(e)
    for build in builds:
        print(f"[reader] {name}: {build[0].program}: " + ", ".join(
            f"{e.phase} {e.seconds:.3f} s"
            + (f" (cache {e.cache})" if e.phase == "compile" else "")
            for e in build), flush=True)
    for e in events:
        if e.phase == "trace" and e.program == metric["program"]:
            own = e.seconds - sum(seconds for _, seconds, _
                                  in e.nested + e.kernels)
            print(f"[reader] {name}: trace of {e.program}, {e.seconds:.3f} "
                  f"s, {own:.3f} its own; own seconds (times traced) of "
                  f"the largest of what was traced inside it: " + ", ".join(
                      f"{function} {seconds:.3f} ({times})"
                      for function, seconds, times in e.nested[:10])
                  + "; kernels, whole seconds under kernel_trace (times): "
                  + (", ".join(f"{kernel} {seconds:.3f} ({times})"
                               for kernel, seconds, times in e.kernels[:10])
                     or "none"), flush=True)


def read(metric, run):
    registry = device_programs()
    if (registry is None or not hasattr(registry, "builds")
            or not run["trace"].devices):
        return None
    events = registry.builds(until=run["window"][0])
    if metric.get("commentary"):
        say(metric, events)
    return float(QUANTITIES[metric["quantity"]](events, run))
