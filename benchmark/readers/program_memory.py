"""What the noted executable of ``program`` needs of one device, as a
share of that device's memory, %: arguments + outputs - aliased outputs +
temporaries + generated code by the executable's own ``memory_analysis()``
(``ray_tpu.observability.device_programs.memory_of``; per device on a
mesh), over the ``bytes_limit`` of the fullest device. The rows a step
can take hang on it; ``memory_peak_bytes`` in the result counts the
donated arguments only.

Nothing where the program keeps no such record or noted no executable of
that name, and nothing where the trace shows no device (a rehearsal on
the CPU), like the trace's readers.
"""

from benchmark.readers._registry import device_programs


def needed_bytes(memory: dict) -> int:
    return (memory["argument"] + memory["output"] - memory["alias"]
            + memory["temp"] + memory["generated_code"])


def read(metric, run):
    registry = device_programs()
    if (registry is None or not hasattr(registry, "memory_of")
            or not run["trace"].devices):
        return None
    memory = registry.memory_of(metric["program"])
    if not memory:
        return None
    import jax

    fullest = max((d.memory_stats() or {} for d in jax.local_devices()),
                  key=lambda stats: stats.get("peak_bytes_in_use", 0))
    limit = fullest.get("bytes_limit")
    if not limit:
        return None
    needed = needed_bytes(memory)
    print(f"[reader] {metric['name']}: {metric['program']} needs {needed} "
          f"B of a device's {limit}: " + ", ".join(
              f"{kind} {value}" for kind, value in memory.items()),
          flush=True)
    return 100.0 * needed / limit
