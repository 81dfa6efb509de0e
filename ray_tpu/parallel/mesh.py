"""Device mesh management for multi-axis parallelism.

The TPU-native replacement for the reference's process-group plumbing
(util/collective group bootstrap, train/backend.py worker-group wiring):
instead of N processes rendezvousing NCCL communicators, a single SPMD
program runs over a `jax.sharding.Mesh` whose named axes carry the
parallelism kinds:

  dp  data parallelism (batch sharding; FSDP rides this axis too)
  pp  pipeline parallelism (layer stages)
  sp  sequence/context parallelism (ring attention over ICI neighbors)
  tp  tensor parallelism (heads / hidden sharding)

Expert parallelism (ep) is to ride the dp axis (GShard/Switch convention:
experts distributed over data-parallel ranks): the rule is here, the
exchange of tokens between the ranks is not built yet. Axis sizes multiply
to the device count; size-1 axes are legal and compile away.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_ORDER = ("dp", "pp", "sp", "tp")

# Canonical logical-axis -> mesh-axis rules for transformer state.
# (the moral equivalent of the reference's per-backend device placement,
# but declarative; see models/transformer.py for use)
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "batch": "dp",
    "seq": "sp",
    "heads": "tp",
    "kv_heads": "tp",
    "hidden": None,
    "mlp": "tp",
    "vocab": "tp",
    "layers": "pp",
    # the ``E`` kind's held experts (models/transformer.py::_kind_leaves);
    # no step runs them over dp > 1 until the tokens' all-to-all exists
    # (models/training.py::_refuse_unbuilt)
    "experts": "dp",
    "ssm_heads": "tp",  # a Mamba-2 mixer's heads (and what is per head)
    "stage": "pp",
}


@dataclass(frozen=True)
class MeshSpec:
    """A named factorization of the device count."""

    dp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.sp * self.tp

    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.dp, self.pp, self.sp, self.tp)

    @classmethod
    def auto(cls, n_devices: int, *, want_pp: bool = True,
             want_sp: bool = True, want_tp: bool = True) -> "MeshSpec":
        """Greedy factorization: give tp, then sp, then pp a factor of 2
        each (ICI-neighbor axes first), remainder to dp."""
        remaining = n_devices
        tp = 2 if want_tp and remaining % 2 == 0 and remaining >= 2 else 1
        remaining //= tp
        sp = 2 if want_sp and remaining % 2 == 0 and remaining >= 2 else 1
        remaining //= sp
        pp = 2 if want_pp and remaining % 2 == 0 and remaining >= 2 else 1
        remaining //= pp
        return cls(dp=remaining, pp=pp, sp=sp, tp=tp)


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < spec.size:
        raise ValueError(
            f"mesh needs {spec.size} devices, have {len(devices)}")
    arr = np.array(devices[: spec.size]).reshape(spec.axis_sizes())
    return Mesh(arr, AXIS_ORDER)


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[Dict[str, Optional[str]]] = None) -> P:
    """Map logical array axes to a PartitionSpec through the rule table."""
    rules = rules or DEFAULT_RULES
    parts = []
    for ax in logical_axes:
        if ax is None:
            parts.append(None)
        else:
            parts.append(rules.get(ax))
    return P(*parts)


def sharding_for(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                 rules: Optional[Dict[str, Optional[str]]] = None
                 ) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules))


def fsdp_rules(rules: Optional[Dict[str, Optional[str]]] = None
               ) -> Dict[str, Optional[str]]:
    """Variant rule table that additionally shards parameters' hidden axis
    over dp — fully-sharded data parallelism."""
    out = dict(rules or DEFAULT_RULES)
    out["hidden"] = "dp"
    return out
