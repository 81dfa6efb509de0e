"""ray_tpu.parallel — mesh, sharding, and parallelism primitives."""

from jax import lax as _lax


def pvary(x, axis_names):
    """Mark a constant as device-varying over mesh axes (needed for
    shard_map scan carries)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    return _lax.pcast(x, tuple(axis_names), to="varying")


from ray_tpu.parallel.mesh import (  # noqa: F401,E402
    AXIS_ORDER,
    DEFAULT_RULES,
    MeshSpec,
    build_mesh,
    fsdp_rules,
    sharding_for,
    spec_for,
)
from ray_tpu.parallel.pipeline import pipeline_spmd  # noqa: F401,E402
from ray_tpu.parallel.ring_attention import (  # noqa: F401,E402
    local_attention,
    ring_attention,
)
