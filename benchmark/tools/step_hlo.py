"""Whether two checkouts compile a cell's train step to the same code.

Builds the cell's step as the driver does (``model_config``, ``mesh_of``,
the driver's shardings) over shapes only, compiles it for the attached
chips (or, with ``--topology v5e:2x2``, for a described chip here on the
CPU), and writes to ``--out``:

- ``memory_analysis``: the compiler's byte counts;
- ``hlo_sha256``: a digest of the optimized HLO with each instruction's
  ``metadata={...}``, the module's tables of source locations and its
  name taken out, and each Pallas kernel's serialized Mosaic module put
  as its text without source locations (they hold the call stack's file
  lines), so that scopes and a renamed program read the same and any
  other change does not;
- ``renamed_sha256``: the same with every ``%name`` replaced by the order
  of its first appearance, which still compares where the instructions'
  numbers shifted;
- ``opcodes``: instructions counted by opcode and result shape.

    python3 -m benchmark.tools.step_hlo --workload W --out FILE.json
        [--topology v5e:2x2] [--hlo FILE.txt]

Run it in both checkouts and compare the two files.
"""

import argparse
import base64
import hashlib
import json
import re
import time
from collections import Counter

from benchmark import loader
from benchmark.drivers.train_steps import mesh_of, model_config

METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
# the module's tables of source files and stack frames, which the
# instructions' metadata points into
FRAME_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*",
    re.MULTILINE)
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+?) ?([a-z][\w\-]*)\(", re.MULTILINE)
KERNEL_BODY = re.compile(r'("custom_call_config":\{"body":")([A-Za-z0-9+/=]+)"')


def kernel_digest(match) -> str:
    """A serialized Mosaic module as the digest of its text without the
    source locations."""
    from jax._src.lib.mlir import ir

    context = ir.Context()
    context.allow_unregistered_dialects = True
    with context:
        module = ir.Module.parse(base64.b64decode(match.group(2)))
        text = module.operation.get_asm(enable_debug_info=False)
    return (match.group(1) + "sha256:"
            + hashlib.sha256(text.encode()).hexdigest() + '"')


def stripped(hlo_text: str) -> str:
    text = FRAME_TABLES.sub("", METADATA.sub("", hlo_text))
    text = KERNEL_BODY.sub(kernel_digest, text)
    return re.sub(r"^HloModule \S+", "HloModule _,", text, count=1)


def renamed(plain: str) -> str:
    order = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: order.setdefault(m.group(0), f"%{len(order)}"),
                  plain)


def compile_step(cell, topology=None):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.training import (
        build_train_step,
        make_optimizer,
        param_shardings,
    )
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    config, mix = cell.config, cell.workload
    axes, fsdp = mesh_of(config)
    devices = None
    if topology:
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(
            platform="tpu", topology_name=topology).devices[:cell.chips]
    mesh = build_mesh(MeshSpec(**axes), devices)
    mcfg = model_config(config, mix["seq"])
    hp = config["run"]["optimizer"]
    optimizer = make_optimizer(
        learning_rate=hp["learning_rate"], weight_decay=hp["weight_decay"],
        b1=hp["b1"], b2=hp["b2"], grad_clip=hp["grad_clip"])
    step, init = build_train_step(mcfg, mesh, fsdp=fsdp, optimizer=optimizer)
    p_shard = param_shardings(mcfg, mesh, fsdp=fsdp)
    params, opt_state = jax.eval_shape(init, jax.random.PRNGKey(0))
    opt_shard = optax.tree_map_params(
        optimizer, lambda _, s: s, opt_state, p_shard,
        transform_non_params=lambda _: NamedSharding(mesh, P()))

    def placed(shapes, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            shapes, shardings)

    tokens = jax.ShapeDtypeStruct(
        (mix["batch"], mix["seq"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", None)))
    return step.lower(placed(params, p_shard), placed(opt_state, opt_shard),
                      tokens).compile()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--topology", default=None)
    parser.add_argument("--hlo", default=None,
                        help="also write the stripped HLO text here")
    args = parser.parse_args()
    t0 = time.perf_counter()
    compiled = compile_step(loader.Cell(args.workload), args.topology)
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    plain = stripped(text)
    memory = compiled.memory_analysis()
    out = {
        "workload": args.workload, "compile_s": seconds,
        "module": text.split(",", 1)[0],
        "memory_analysis": {k: getattr(memory, k) for k in dir(memory)
                            if k.endswith("_in_bytes")},
        "hlo_bytes": len(text),
        "hlo_sha256": hashlib.sha256(plain.encode()).hexdigest(),
        "renamed_sha256": hashlib.sha256(
            renamed(plain).encode()).hexdigest(),
        "opcodes": dict(sorted(Counter(
            f"{op} {shape}" for shape, op in INSTRUCTION.findall(plain)
        ).items())),
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(plain)
    print(json.dumps({k: v for k, v in out.items() if k != "opcodes"}),
          flush=True)


if __name__ == "__main__":
    main()
