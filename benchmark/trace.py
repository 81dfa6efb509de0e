"""Reduction of a JAX profiler trace (``.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What it
takes from the trace:

- the traced window: the harness's own ``bench:window`` annotation on the
  host, on the same clock as the device's events;
- per device plane (``/device:TPU:<n>``), the events of the line
  ``XLA Ops`` clipped to the window: their union is the time in which an
  operation ran on that device (``busy_s``; averaged over the devices),
  each event's time less that of the events nested in it is the
  operation's own time, and the events matching a pattern
  give a kernel's time and count;
- the events of the line ``XLA Modules``: one a run of a jitted program;
- the idle gaps of device 0, longest first, each named after the harness
  span (``bench:<name>`` annotations on the host) that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
SPAN_PREFIX = "bench:"
WINDOW = SPAN_PREFIX + "window"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%flash_fwd.19 = (bf16[...]) custom-call(...)``: the instruction's
    own name is what comes before `` = ``. A Pallas kernel keeps the name
    its ``pallas_call`` was given, wrapped in the transforms it went
    through (``%transpose_jvp_flash_bwd_dq__.1``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals):
    """Total length of the union of (start, end) intervals, and the gaps
    between them as (start, end)."""
    total, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def uncovered(intervals, cover) -> int:
    """Length of the union of ``intervals`` that no interval of ``cover``
    overlaps."""
    both, _ = union(list(intervals) + list(cover))
    return both - union(cover)[0]


def innermost(events):
    """The events of one line that have no event nested in them."""
    out, stack = [], []  # stack of [end, has a child, event]
    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= ev[0]:
            end, parent, done = stack.pop()
            if not parent:
                out.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([ev[1], False, ev])
    out.extend(done for _end, parent, done in stack if not parent)
    return out


def self_times(events):
    """(name, own nanoseconds) per event of one line: its duration less
    the events nested in it (a loop's body lies inside the loop's event)."""
    out, stack = [], []  # stack of [end, index into out]
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(end, stack[-1][0]) - start
        out.append([name, end - start])
        stack.append([end, len(out) - 1])
    return [(name, max(ns, 0)) for name, ns in out]


class Trace:
    """What the readers under ``benchmark/readers`` may ask of a trace."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.devices = {}   # device number -> [(start, end, op name)]
        self.modules = {}   # device number -> [(start, end, program name)]
        self.async_ops = {}  # device number -> [(start, end, op name)]
        self.host_spans = []  # (start, end, name) of bench:* annotations
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m:
                    if line.name == OPS_LINE:
                        dest = self.devices.setdefault(int(m.group(1)), [])
                    elif line.name == MODULES_LINE:
                        dest = self.modules.setdefault(int(m.group(1)), [])
                    elif line.name == ASYNC_LINE:
                        dest = self.async_ops.setdefault(int(m.group(1)), [])
                    else:
                        continue
                    for ev in line.events:
                        s = int(ev.start_ns)
                        dest.append((s, s + int(ev.duration_ns),
                                     op_name(ev.name)))
                else:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            s = int(ev.start_ns)
                            self.host_spans.append(
                                (s, s + int(ev.duration_ns), ev.name))
        windows = [(s, e) for s, e, n in self.host_spans if n == WINDOW]
        if windows:
            self.window = (min(s for s, _ in windows),
                           max(e for _, e in windows))
        else:  # a trace recorded without the harness: first to last event
            every = [x for evs in self.devices.values() for x in evs]
            self.window = ((min(e[0] for e in every), max(e[1] for e in every))
                           if every else (0, 0))
        w0, w1 = self.window
        for lines in (self.devices, self.modules, self.async_ops):
            for n, evs in lines.items():
                lines[n] = [(max(s, w0), min(e, w1), name)
                            for s, e, name in evs if e > w0 and s < w1]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        busy = [union([(s, e) for s, e, _ in evs])[0]
                for evs in self.devices.values()]
        return sum(busy) / len(busy) / 1e9

    def matching(self, pattern: str, line: str = "ops"):
        """(seconds, events) of the operations (``line="ops"``) or whole
        jitted programs (``line="modules"``, named ``jit_<fn>(<id>)``)
        whose name matches, averaged over the devices."""
        rx = re.compile(pattern)
        ns = count = 0
        for evs in (self.modules if line == "modules"
                    else self.devices).values():
            for s, e, name in evs:
                if rx.search(name):
                    ns += e - s
                    count += 1
        n = max(len(self.devices), 1)
        return ns / n / 1e9, count / n

    def collective_exposed_s(self):
        """(seconds of collective operations, seconds of them during
        which no other operation ran on that device), averaged over the
        devices. Collectives are the synchronous ones on ``XLA Ops`` and
        the start-to-done spans on ``Async XLA Ops``."""
        total = exposed = 0
        for n, evs in self.devices.items():
            leaves = innermost(evs)
            compute = [(s, e) for s, e, name in leaves
                       if not COLLECTIVE.match(name)]
            comm = [(s, e) for s, e, name in
                    leaves + self.async_ops.get(n, [])
                    if COLLECTIVE.match(name)]
            total += union(comm)[0]
            exposed += uncovered(comm, compute)
        n = max(len(self.devices), 1)
        return total / n / 1e9, exposed / n / 1e9

    def top_ops(self, limit: int = 10):
        """[[name, own seconds]] of device 0's operations, longest first,
        instances of one name summed."""
        if not self.devices:
            return []
        evs = self.devices[min(self.devices)]
        total = defaultdict(int)
        for name, ns in self_times(evs):
            total[name] += ns
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / 1e9] for name, ns in ranked]

    def idle_gaps(self, limit: int = 10):
        """[[span name, seconds]] of device 0's longest idle gaps inside
        the window, each named after the harness span that covers most of
        it (``host`` where none does)."""
        if not self.devices:
            return []
        evs = self.devices[min(self.devices)]
        _, gaps = union([(s, e) for s, e, _ in evs])
        w0, w1 = self.window
        if evs:
            first = min(s for s, _, _ in evs)
            last = max(e for _, e, _ in evs)
            gaps = [(w0, first)] + gaps + [(last, w1)]
        else:
            gaps = [(w0, w1)]
        spans = [x for x in self.host_spans if x[2] != WINDOW]
        out = []
        for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:limit]:
            if g1 <= g0:
                continue
            cover = defaultdict(int)
            for s, e, name in spans:
                overlap = min(e, g1) - max(s, g0)
                if overlap > 0:
                    cover[name[len(SPAN_PREFIX):]] += overlap
            name = max(cover, key=cover.get) if cover else "host"
            out.append([name, (g1 - g0) / 1e9])
        return out
