"""The program's registry of its compiled device programs
(``ray_tpu.observability.device_programs``), or None for a program from
before it: a reader of it then reports nothing."""


def device_programs():
    try:
        from ray_tpu.observability import device_programs as registry
    except ImportError:
        return None
    return registry
