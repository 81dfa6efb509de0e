"""Registry of the process's compiled device programs.

What only the process that built a program knows, kept where a reader
(the benchmark, chip_smoke.py, a person with a trace) can ask:

- build events: every phase of every program's build that JAX reports,
  by program name, with the ``time.perf_counter()`` at its end and its
  seconds. ``trace`` (the outermost trace of a program: the functions
  and kernels traced inside it are folded into it as a bounded table of
  own times), ``lower`` (to an MLIR module; Pallas builds each kernel's
  Mosaic module here), ``compile`` (the backend's, with whether the
  persistent compilation cache answered it; on a hit its seconds are the
  key's hashing plus the read) and ``cache_read`` (the read, decompress,
  deserialize and load inside a compile that hit). ``builds(since,
  until)`` gives those inside an interval and ``compiles(since, until)``
  the compiles alone; ``device_program_compiles{program, cache}`` counts
  them, ``device_program_build_seconds{program, phase}`` sums them.
  Inside a measured window the count should be 0.
- kernel traces: Pallas traces a kernel's body outside ``jit``, so no
  event of JAX's names it. ``kernel_trace(kernel)`` around the
  construction of a ``pallas_call`` takes its host seconds into
  ``device_program_kernel_trace_seconds{kernel}`` and into the build
  event of the program being traced (``kernels``). It runs only while
  Python traces.
- scope tables: a profiler trace names a device operation by its HLO
  instruction (``fusion.387``), which changes with every compile and
  belongs to no layer. The compiled module's own text carries, per
  instruction, ``metadata={op_name="jit(train_step)/transpose(jvp(
  layers))/while/body/.../mlp/dot_general"}``: the ``jax.named_scope``s
  of the program and the pass (``jvp(`` forward, ``rematted_computation``
  recompute, ``transpose(jvp(`` backward). ``note(name, compiled)`` keeps
  the newest ``Compiled`` per program; ``scope_table_of(name)`` parses
  its text when first asked; ``anatomy`` joins a trace's events with it.
- memory: ``note`` also reads the executable's ``memory_analysis()``
  into ``device_program_memory_bytes{program, kind}``; ``memory_of(name)``
  returns it.

Where ``util/tracing.setup_tracing()`` is on, each build event is also a
finished span ``device_program.<phase>`` of that span system.

Importing this module registers three ``jax.monitoring`` listeners and
nothing else; no text is parsed until a table is asked for. A step
traces thousands of functions, and each is two callbacks here (its start
and its end): they make and keep nothing but a frame.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax

from ray_tpu.observability.metrics import (
    device_program_build_seconds,
    device_program_compiles,
    device_program_kernel_trace_seconds,
    device_program_memory_bytes,
)
from ray_tpu.util import tracing

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_PHASES = {_TRACE: "trace", _LOWER: "lower", _BACKEND_COMPILE: "compile"}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_MAX_EVENTS = 4096
# the functions a trace event names, those with the largest own times
_MAX_NESTED = 32
_OTHERS = "(others)"
_MEMORY_KINDS = ("argument", "output", "alias", "temp", "generated_code")

_lock = threading.Lock()
_events: deque = deque(maxlen=_MAX_EVENTS)
_noted: Dict[str, Any] = {}             # program -> newest Compiled
_tables: Dict[str, Dict[str, str]] = {}  # program -> parsed scope table
_memory: Dict[str, Dict[str, int]] = {}  # program -> memory_analysis()
# what is open on the building thread. ``cache`` / ``cache_read``: the
# cache's nameless events fire inside the named compile event, and are
# held until that event closes. ``frames``: the traces, lowerings and
# kernel traces that have begun and not ended, outermost first.
_pending = threading.local()


class CompileEvent(NamedTuple):
    program: str    # "train_step" for jit(train_step)
    at: float       # time.perf_counter() when the compile ended
    seconds: float
    cache: str      # "hit" | "miss" | "off" (no persistent cache asked)


class BuildEvent(NamedTuple):
    program: str
    phase: str      # "trace" | "lower" | "compile" | "cache_read"
    at: float       # time.perf_counter() when the phase ended
    seconds: float
    cache: str      # of a compile and its cache_read, as CompileEvent's
    # of a trace or a lowering: ((function, own seconds, times traced),
    # ...) of what was traced inside it, largest own time first, at most
    # _MAX_NESTED and the rest summed under "(others)"; a program built
    # meanwhile is "jit(<name>)". A function JAX found in its own cache
    # reads about 0 s and still counts
    nested: Tuple[Tuple[str, float, int], ...] = ()
    # of a trace: ((kernel, seconds under kernel_trace, times), ...),
    # with all that a kernel's body traced as the kernel's
    kernels: Tuple[Tuple[str, float, int], ...] = ()


# A trace, lowering or kernel trace that has begun, and what ended inside
# it: [name, seconds of what ended directly inside, {function: [own
# seconds, times traced]} or None, {kernel: [seconds, times]} or None].
# A list and not a class, made and folded in a few plain statements: a
# step opens thousands of these, two callbacks each.
_NAME, _INSIDE, _NESTED, _KERNELS = range(4)


def _add(table: Dict[str, List], name: str, seconds: float,
         times: int = 1) -> None:
    entry = table.get(name)
    if entry is None:
        table[name] = [seconds, times]
    else:
        entry[0] += seconds
        entry[1] += times


def _ended_inside(parent: List, name: str, seconds: float,
                  own: float) -> None:
    """``name`` took ``seconds`` directly inside ``parent``, ``own`` of
    them not inside anything named there."""
    parent[_INSIDE] += seconds
    nested = parent[_NESTED]
    if nested is None:
        nested = parent[_NESTED] = {}
    entry = nested.get(name)
    if entry is None:
        nested[name] = [own, 1]
    else:
        entry[0] += own
        entry[1] += 1


def _close_into(parent: List, frame: List, seconds: float) -> None:
    """``frame`` took ``seconds`` and ended inside ``parent``."""
    _ended_inside(parent, frame[_NAME], seconds, seconds - frame[_INSIDE])
    if frame[_NESTED]:
        nested = parent[_NESTED]
        for name, (own, times) in frame[_NESTED].items():
            _add(nested, name, own, times)
        if len(nested) > 2 * _MAX_NESTED:
            parent[_NESTED] = {name: [own, times] for name, own, times
                               in _largest(nested)}
    if frame[_KERNELS]:
        if parent[_KERNELS] is None:
            parent[_KERNELS] = {}
        for name, (whole, times) in frame[_KERNELS].items():
            _add(parent[_KERNELS], name, whole, times)


def _largest(table: Optional[Dict[str, List]]
             ) -> Tuple[Tuple[str, float, int], ...]:
    """The table's ``_MAX_NESTED`` largest seconds, largest first, and
    the rest as one entry."""
    rows = sorted(((name, seconds, times) for name, (seconds, times)
                   in (table or {}).items()),
                  key=lambda row: (row[0] == _OTHERS, -row[1]))
    kept, rest = rows[:_MAX_NESTED], rows[_MAX_NESTED:]
    if rest:
        kept.append((_OTHERS, sum(r[1] for r in rest),
                     sum(r[2] for r in rest)))
    return tuple(kept)


def program_name(fun_name: str) -> str:
    """``jit(train_step)`` -> ``train_step``: the name the function was
    given, as ``note`` takes it and the trace's ``jit_train_step`` has it."""
    m = re.fullmatch(r"\w+\((.*)\)", fun_name)
    return m.group(1) if m else fun_name


def _frames() -> List[List]:
    try:
        return _pending.frames
    except AttributeError:
        frames = _pending.frames = []
        return frames


def _on_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _pending.cache = outcome


def _on_scalar(event: str, _value=None, fun_name: str = "", **_kw) -> None:
    # JAX reports the start of a phase as a scalar of the phase's name.
    # Lowering traces too (a rule written as a jnp function), with no
    # trace open around it
    if event == _TRACE or event == _LOWER:
        _frames().append([fun_name, 0.0, None, None])


def _on_duration(event: str, seconds: float, fun_name: str = "",
                 **_kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        if event == _CACHE_READ:
            _pending.cache_read = seconds
        return
    frames = _frames()
    nested = kernels = ()
    if phase != "compile":  # it began with a frame
        frame = None
        while frames:
            frame = frames.pop()
            if frame[_NAME] == fun_name:
                break
        if frame is None:  # a phase whose start nobody reported
            frame = [fun_name, 0.0, None, None]
        if frames and phase == "trace":  # inside another: folded, not kept
            _close_into(frames[-1], frame, seconds)
            return
        nested = _largest(frame[_NESTED])
        kernels = _largest(frame[_KERNELS])
    if frames:
        # built while a program is traced (an eager operation on a
        # constant): an event of its own, off the tracing function's own
        # time
        _ended_inside(frames[-1], fun_name, seconds, seconds)
    cache, read = "", None
    if phase == "compile":
        cache = getattr(_pending, "cache", "off")
        read = getattr(_pending, "cache_read", None)
        _pending.cache, _pending.cache_read = "off", None
    done = BuildEvent(program_name(fun_name), phase, time.perf_counter(),
                      seconds, cache, nested, kernels)
    kept = [done]
    if read is not None and cache == "hit":
        kept.append(done._replace(phase="cache_read", seconds=read))
    with _lock:
        _events.extend(kept)
    for e in kept:
        device_program_build_seconds.inc(
            e.seconds, tags={"program": e.program, "phase": e.phase})
    if phase == "compile":
        device_program_compiles.inc(
            tags={"program": done.program, "cache": cache})
    if tracing.enabled():
        # one span system: a worker's builds between its tasks. The
        # phase's wall start; its end is now, as tracing stamps it
        for e in kept:
            tracing.record_span_tree(
                f"device_program.{e.phase}", time.time() - e.seconds, (),
                {"program": e.program, "cache": e.cache})


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_scalar_listener(_on_scalar)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


@contextlib.contextmanager
def kernel_trace(kernel: str):
    """Around the construction of a ``pallas_call``: the host seconds
    Pallas takes to trace the kernel's body, which no event of JAX's
    names, into ``device_program_kernel_trace_seconds{kernel}`` and into
    the trace that is open on this thread: its ``kernels``, and its
    ``nested`` as ``pallas_call(<kernel>)`` with all that was traced
    inside as the kernel's own. Entered only while Python traces: a
    compiled program never comes here."""
    frames = _frames()
    frame = [kernel, 0.0, None, None]
    frames.append(frame)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        seconds = time.perf_counter() - t0
        while frames and frames.pop() is not frame:
            pass
        device_program_kernel_trace_seconds.inc(
            seconds, tags={"kernel": kernel})
        if frames:  # all that its body traced is the kernel's
            parent = frames[-1]
            parent[_INSIDE] += seconds
            if parent[_KERNELS] is None:
                parent[_KERNELS] = {}
            _add(parent[_KERNELS], kernel, seconds)


def builds(since: float = 0.0, until: float = float("inf")
           ) -> List[BuildEvent]:
    """The build events that ended inside [since, until] on
    ``time.perf_counter()``, oldest first (the ring keeps the newest
    4096 of all phases)."""
    with _lock:
        return [e for e in _events if since <= e.at <= until]


def compiles(since: float = 0.0, until: float = float("inf")
             ) -> List[CompileEvent]:
    """The compile events among ``builds(since, until)``."""
    return [CompileEvent(e.program, e.at, e.seconds, e.cache)
            for e in builds(since, until) if e.phase == "compile"]


# ------------------------------------------------------------ scope tables
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bmetadata=\{[^}]*?op_name="([^"]*)"',
    re.MULTILINE)


def scope_table(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name path} of a compiled module's text
    (``Compiled.as_text()``). A fusion has its own ``metadata``, its
    root's; an instruction without ``op_name`` is not in the table."""
    return {m.group(1): m.group(2) for m in _INSTRUCTION.finditer(hlo_text)}


def note(name: str, compiled) -> None:
    """Keep ``compiled`` as the newest executable of program ``name``,
    and what its ``memory_analysis()`` says it needs of a device."""
    # (anything with ``as_text`` can be noted: it then has no memory)
    analyse = getattr(compiled, "memory_analysis", None)
    analysis = analyse() if analyse is not None else None
    memory = {}
    if analysis is not None:
        memory = {kind: int(getattr(analysis, f"{kind}_size_in_bytes"))
                  for kind in _MEMORY_KINDS}
        # the most live at once, arguments included: where the backend
        # says so
        peak = getattr(analysis, "peak_memory_in_bytes", None)
        if peak is not None:
            memory["peak"] = int(peak)
    with _lock:
        _noted[name] = compiled
        _tables.pop(name, None)
        _memory[name] = memory
    for kind, value in memory.items():
        device_program_memory_bytes.set(
            value, tags={"program": name, "kind": kind})


def memory_of(name: str) -> Optional[Dict[str, int]]:
    """{kind: bytes} of the newest noted ``Compiled`` of that program on
    one device (``argument``, ``output``, ``alias``, ``temp``,
    ``generated_code``, and ``peak`` where the backend reports it); None
    where none was noted or the backend analyses nothing."""
    with _lock:
        return dict(_memory[name]) if _memory.get(name) else None


def scope_table_of(name: str) -> Optional[Dict[str, str]]:
    """The scope table of the newest noted ``Compiled`` of that program,
    parsed on the first call; None where none was noted."""
    with _lock:
        table, compiled = _tables.get(name), _noted.get(name)
    if table is None and compiled is not None:
        table = scope_table(compiled.as_text())
        with _lock:
            if _noted.get(name) is compiled:
                _tables[name] = table
    return table


def clear() -> None:
    """Forget every noted program and build event (tests)."""
    with _lock:
        _events.clear()
        _noted.clear()
        _tables.clear()
        _memory.clear()


def anatomy(events: Iterable[Tuple[int, int, str]], program: str
            ) -> Dict[str, float]:
    """{op_name path: own seconds} of one device's ``(start ns, end ns,
    instruction name)`` events, as a trace's ``XLA Ops`` line has them.
    An event's own time is its duration less the events nested in it (a
    ``while`` without its body), so the values sum to the device's busy
    time; instructions the table does not know are summed under ``""``."""
    table = scope_table_of(program) or {}
    own: Dict[str, float] = {}
    stack: List[List] = []  # [end, path, own ns] of the open events

    def close():
        _end, path, ns = stack.pop()
        own[path] = own.get(path, 0.0) + max(ns, 0) / 1e9

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close()
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, table.get(name, ""), end - start])
    while stack:
        close()
    return own


# ------------------------------------------------- programs with a name
def named_jit(fun: Callable, name: str, **jit_kwargs):
    """``jax.jit(fun)`` under a stable program name: the trace's ``XLA
    Modules`` line reads ``jit_<name>``, compile events ``<name>``."""
    fun.__name__ = fun.__qualname__ = name
    return jax.jit(fun, **jit_kwargs)


class _Forwarding:
    def __init__(self, inner, name: str):
        self._inner, self._name = inner, name

    def __getattr__(self, attr):
        return getattr(self._inner, attr)


class Noted(_Forwarding):
    """A jitted program whose explicit ``lower(...).compile()`` notes the
    ``Compiled`` under the program's name, so that the executable a
    caller runs is the one whose scope table is read. Calls and every
    other attribute go to the jitted function as they are; a direct call
    compiles inside JAX and notes nothing."""

    def __init__(self, jitted):
        super().__init__(jitted, jitted.__name__)

    def __call__(self, *args, **kwargs):
        return self._inner(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return _NotedLowered(self._inner.lower(*args, **kwargs), self._name)


class _NotedLowered(_Forwarding):
    def compile(self, *args, **kwargs):
        compiled = self._inner.compile(*args, **kwargs)
        note(self._name, compiled)
        return compiled
