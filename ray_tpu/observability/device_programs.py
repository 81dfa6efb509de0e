"""Registry of the process's compiled device programs.

Two things only the process that compiled a program knows, kept where a
reader (the benchmark, chip_smoke.py, a person with a trace) can ask:

- compile events: every backend compile JAX reports, by program name,
  with the ``time.perf_counter()`` at its end, its seconds and whether
  the persistent compilation cache answered it. ``compiles(since, until)``
  gives those inside an interval; ``device_program_compiles{program,
  cache}`` counts them. Inside a measured window the count should be 0.
- scope tables: a profiler trace names a device operation by its HLO
  instruction (``fusion.387``), which changes with every compile and
  belongs to no layer. The compiled module's own text carries, per
  instruction, ``metadata={op_name="jit(train_step)/transpose(jvp(
  layers))/while/body/.../mlp/dot_general"}``: the ``jax.named_scope``s
  of the program and the pass (``jvp(`` forward, ``rematted_computation``
  recompute, ``transpose(jvp(`` backward). ``note(name, compiled)`` keeps
  the newest ``Compiled`` per program; ``scope_table_of(name)`` parses
  its text when first asked; ``anatomy`` joins a trace's events with it.

Importing this module registers the two ``jax.monitoring`` listeners and
nothing else; no text is parsed until a table is asked for.
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax

from ray_tpu.observability.metrics import device_program_compiles

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_MAX_EVENTS = 4096

_lock = threading.Lock()
_events: deque = deque(maxlen=_MAX_EVENTS)
_noted: Dict[str, Any] = {}             # program -> newest Compiled
_tables: Dict[str, Dict[str, str]] = {}  # program -> parsed scope table
# the cache's nameless hit/miss event fires inside the named compile
# event, on the compiling thread: held here until that event closes
_pending = threading.local()


class CompileEvent(NamedTuple):
    program: str    # "train_step" for jit(train_step)
    at: float       # time.perf_counter() when the compile ended
    seconds: float
    cache: str      # "hit" | "miss" | "off" (no persistent cache asked)


def program_name(fun_name: str) -> str:
    """``jit(train_step)`` -> ``train_step``: the name the function was
    given, as ``note`` takes it and the trace's ``jit_train_step`` has it."""
    m = re.fullmatch(r"\w+\((.*)\)", fun_name)
    return m.group(1) if m else fun_name


def _on_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _pending.cache = outcome


def _on_duration(event: str, seconds: float, fun_name: str = "",
                 **_kw) -> None:
    if event != _BACKEND_COMPILE:
        return
    cache = getattr(_pending, "cache", "off")
    _pending.cache = "off"
    done = CompileEvent(program_name(fun_name), time.perf_counter(),
                        seconds, cache)
    with _lock:
        _events.append(done)
    device_program_compiles.inc(
        tags={"program": done.program, "cache": cache})


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles(since: float = 0.0, until: float = float("inf")
             ) -> List[CompileEvent]:
    """The compile events that ended inside [since, until] on
    ``time.perf_counter()``, oldest first (the ring keeps the newest
    4096)."""
    with _lock:
        return [e for e in _events if since <= e.at <= until]


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
    """Keep ``compiled`` as the newest executable of program ``name``."""
    with _lock:
        _noted[name] = compiled
        _tables.pop(name, None)


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
    """Forget every noted program and compile event (tests)."""
    with _lock:
        _events.clear()
        _noted.clear()
        _tables.clear()


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
