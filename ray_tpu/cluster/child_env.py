"""Environment of every child process this package starts.

One process owns the chip: the one that called ``ray_tpu.init()``. It
hosts the in-process tier — the raylets whose scheduling tick runs the
jitted solve, and the thread workers a ``train.Trainer`` function runs
on — so every device program of a session lives in that process. An
accelerator belongs to one process at a time; a second process that
opens it fails or hangs.

Hence the rule for children (process workers, raylet and GCS servers,
command-provider nodes): ``JAX_PLATFORMS=cpu``, so a child that imports
JAX never reaches for the parent's chip, and a ``PYTHONPATH`` from which
``ray_tpu`` imports whatever the child's working directory is."""

from __future__ import annotations

import os
from typing import Dict


def package_root() -> str:
    """The directory that holds the ``ray_tpu`` package: the checkout."""
    import ray_tpu

    return os.path.dirname(os.path.dirname(
        os.path.abspath(ray_tpu.__file__)))


def child_env() -> Dict[str, str]:
    """``os.environ`` with the two changes above. The caller's own
    ``PYTHONPATH`` entries stay in front, so user code imports in
    workers and keeps its shadowing priority."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    root = package_root()
    entries = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if root not in entries:
        entries.append(root)
    env["PYTHONPATH"] = os.pathsep.join(entries)
    return env
