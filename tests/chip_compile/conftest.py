"""The main path's device programs, compiled for a v5e that is described
and not attached (on-chip-measurement guide, section 2).

Nothing runs: a compile that passes says the chip's compiler accepts the
program at its real shapes and that the Pallas kernels are in it. What
interpret mode cannot show — tiling, VMEM, HBM fit, "Mosaic kernels
cannot be automatically partitioned" — shows here, at no chip time.

One file a kind of step: the kernels, the dense steps, and one a
benchmark cell's step. Tier-1 runs ``--dist loadfile``, which hands a
worker a file at a time, so a file is the unit the run balances: the
whole steps each take minutes to compile, and in one file they held one
worker for 1368 s of a 1401 s run (PR 41). A new cell's step gets a file
of its own here, on ``cell_step``, and grows none of the others. Each
worker that runs a file here loads the TPU's library (the tier-1
command sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``); the topology is
described in a module-scoped fixture, so no other worker does. There is
no ``__init__.py`` under ``tests/``: a file here needs a basename no
other test file has.
"""

import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the shapes chip_smoke.py runs on the chip
B, S, H, D = 4, 2048, 16, 128

FLASH_UNDER_FULL_REMAT = {"flash_fwd": 2, "flash_bwd_dq": 1,
                          "flash_bwd_dkdv": 1, "attn_delta": 1}
# q and k rotated in the forward, its recompute and the backward
ROPE_UNDER_FULL_REMAT = {"rope_lanes": 6}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it undescribed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device can be written to the persistent
    # cache but not read back without the chip: keep the cache off here
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_tier(monkeypatch):
    """The platform predicate asks ``jax.default_backend()``, which is
    the CPU here: steer it to what a TPU answers (ops/grouped.py asks the
    same one)."""
    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "kernels_on", lambda: True)


def _kernels(compiled) -> dict:
    from chip_smoke import count_kernels

    counts = count_kernels(compiled.as_text())
    return {k: v for k, v in counts.items() if v}


def _struct(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract_train_state(init_fn):
    """(params, opt_state) as init_fn would make them — shapes, with the
    shardings its compiled program gives its outputs: there is no device
    to hold an array. Compiling it also says the chip takes the init
    program."""
    import jax
    import jax.numpy as jnp

    lowered = init_fn.lower(jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree.map(
        lambda info, sharding: _struct(info.shape, info.dtype, sharding),
        lowered.out_info, lowered.compile().output_shardings)


def _tokens(mesh, batch, seq=S):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    return _struct((batch, seq + 1), jnp.int32,
                   NamedSharding(mesh, P("dp", None)))


def cell_config(workload, **overrides):
    """(the configuration the benchmark's cell ``workload`` names, with
    ``overrides``; the cell's workload)."""
    with open(os.path.join(ROOT, "benchmark/workloads",
                           f"{workload}.json")) as f:
        mix = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs",
                           f"{mix['config']}.json")) as f:
        config = json.load(f)
    return dict(config, **overrides), mix


def cell_step(topo, workload, model_config, **overrides):
    """The train step of the benchmark's cell ``workload`` on one
    described chip: its configuration (with ``overrides``) through the
    cell's driver's ``model_config`` at the cell's sequence, under the
    configuration's optimizer (warm-up, carried rounding), as the cell
    runs it -> (step, (params, opt_state) as abstract arrays, the
    tokens of the cell's rows)."""
    from ray_tpu.models.training import build_train_step, make_optimizer
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    config, mix = cell_config(workload, **overrides)
    hp = config["run"]["optimizer"]
    mesh = build_mesh(MeshSpec(), topo.devices[:1])
    step, init_fn = build_train_step(
        model_config(config, mix["seq"]), mesh, optimizer=make_optimizer(
            learning_rate=hp["learning_rate"],
            weight_decay=hp["weight_decay"], b1=hp["b1"], b2=hp["b2"],
            grad_clip=hp["grad_clip"], warmup_steps=hp["warmup_steps"],
            carry=hp["carry_rounding"]))
    return (step, _abstract_train_state(init_fn),
            _tokens(mesh, mix["batch"], mix["seq"]))


def _held(params) -> int:
    """How many parameters ``params`` holds."""
    import jax

    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def _counted(counters, run):
    """What each of ``counters`` counted while ``run()`` ran, by label:
    -> (run's result, [{labels: count added}])."""
    before = [dict(c.series()) for c in counters]
    out = run()
    return out, [{k: v - was.get(k, 0) for k, v in c.series().items()
                  if v != was.get(k, 0)} for c, was in zip(counters, before)]


def _named(text, name, *path):
    """How many kernel calls of the HLO ``text`` are ``name``'s under
    ``path``."""
    return sum('custom_call_target="tpu_custom_call"' in line
               and (f"{name})" in line or f"/{name}/" in line)
               and all(part in line for part in path)
               for line in text.splitlines())


# what the router's choice lowered to before it was a kernel: the sort of
# lax.top_k, the gather of take_along_axis (with its indices' custom
# call) and the scatter of that gather's transpose, as instructions or as
# the primitive an op_name ends in (XLA's rewrite of a scatter keeps its
# op_name on what feeds it)
_ROUTER_LEFT = re.compile(r" (sort|gather|scatter)\(|GatherScatter"
                          r'|/(sort|top_k|gather|scatter[-\w]*)"')


def _router(text):
    """(the ``router_topk_fwd`` kernels of the HLO ``text`` under an
    expert layer's router, its lines there that sort, gather or scatter:
    those whose op_name lies under the scope and those of the
    computations such lines call)."""
    comps = _hlo_computations(text)
    under = [line for lines in comps.values() for line in lines
             if "/moe/router/" in line]
    called = _called_from(comps, [_callee(line, "calls=") for line in under
                                  if "calls=" in line])
    return (_named(text, "router_topk_fwd", "/moe/router/"),
            [line for line in under + [line for name in called
                                       for line in comps.get(name, ())]
             if _ROUTER_LEFT.search(line)])


_COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _hlo_computations(text):
    """Optimized HLO text -> {computation: its instruction lines}."""
    out, name = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            name = name.lstrip("%")
            out[name] = []
        elif name and line.startswith(" "):
            out[name].append(line)
    return out


def _callee(line, key):
    return re.match(r"%?([\w.\-]+)", line.split(key, 1)[1]).group(1)


def _called_from(comps, roots):
    """``roots`` and every computation they call, transitively."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps.get(name, ()):
            for key in ("calls=", "to_apply=", "body=", "condition="):
                if key in line:
                    todo.append(_callee(line, key))
    return seen


def _largest_result(line):
    """Elements of the largest array a collective's result holds."""
    result = line[line.index("=") + 1:_COLLECTIVE.search(line).start()]
    dims = [[int(d) for d in m.split(",") if d]
            for m in re.findall(r"\[([\d,]*)\]", result)]
    return max((int(np.prod(d)) for d in dims), default=1)
