"""The main path's device programs, compiled for a v5e that is described
and not attached (on-chip-measurement guide, section 2).

Nothing runs: a compile that passes says the chip's compiler accepts the
program at its real shapes and that the Pallas kernels are in it. What
interpret mode cannot show — tiling, VMEM, HBM fit, "Mosaic kernels
cannot be automatically partitioned" — shows here, at no chip time.

All of it lives in this one file, and the topology is described inside a
module-scoped fixture: only the worker that is handed this file loads
the TPU's library.
"""

import os
import re

import numpy as np
import pytest

# the shapes chip_smoke.py runs on the chip
B, S, H, D = 4, 2048, 16, 128
N_NODES, N_RES, N_CLASSES = 256, 8, 32


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


@pytest.mark.parametrize("batch,seq,heads,head_dim", [
    (B, S, H, 128),       # chip_smoke's
    (B, S, H, 64),        # half the lanes: forward kernel, blockwise backward
    (4, 4096, 32, 128),   # the cell mistral7b_l4_train_s4096
    (32, 512, 32, 128),   # mistral7b_l4_train_s512
    (2, 4096, 16, 128),   # a chip of mistral7b_l12_train_s4096_4chip
    (1, 16384, 8, 128),   # longer than K/V may stay resident: major blocks
    (2, 8192, 20, 256),   # glm47flash_l7_train_s8192: heads of 192 + 64
])
def test_flash_forward_compiles(one_chip, batch, seq, heads, head_dim):
    """The forward with the blocks its own plan gives the shape: K/V of
    a head resident (or in major blocks), the loop over the keys inside,
    all within the VMEM the plan asks Mosaic for."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A

    plan = A.fwd_block_plan(seq, seq, head_dim, True)
    assert (plan.block_q, plan.block_k) == (A.FWD_BLOCK_Q, A.FWD_BLOCK_K)
    assert plan.block_k_major == min(seq, 4096 * 128 // head_dim)
    assert plan.vmem_bytes < 16 * 2 ** 20
    x = _struct((batch, seq, heads, head_dim), jnp.bfloat16, one_chip)
    fwd = jax.jit(lambda q, k, v: A._pallas_fwd(
        q, k, v, True, head_dim ** -0.5))
    assert _kernels(fwd.lower(x, x, x).compile()) == {"flash_fwd": 1}


def test_flash_forward_compiles_not_causal(one_chip):
    """models/vision.py's call: no mask anywhere, and a sequence that no
    block divides taken whole."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A

    for seq in (1024, 197):
        x = _struct((B, seq, H, 64), jnp.bfloat16, one_chip)
        fwd = jax.jit(lambda q, k, v: A._pallas_fwd(q, k, v, False, 0.125))
        assert _kernels(fwd.lower(x, x, x).compile()) == {"flash_fwd": 1}


@pytest.mark.parametrize("batch,seq,heads,head_dim,causal", [
    (B, S, H, D, True),          # chip_smoke's
    (4, 4096, 32, 128, True),    # the cell mistral7b_l4_train_s4096
    (32, 512, 32, 128, True),    # mistral7b_l4_train_s512
    (2, 4096, 16, 128, True),    # a chip of mistral7b_l12_train_s4096_4chip
    (4, 8192, 32, 128, True),    # nemotron_twotower_l9_train_s8192
    (1, 16384, 8, 128, True),    # major blocks on both kernels
    (2, 8192, 20, 256, True),    # glm47flash_l7_train_s8192: four of them
    (B, S, H, 64, True),         # the kernels called at half the lanes
    (B, 1024, H, 64, False),     # models/vision.py's call: no mask
    (B, 197, H, 64, False),      # a sequence that no block divides, whole
])
def test_flash_backward_compiles(one_chip, batch, seq, heads, head_dim,
                                 causal):
    """The backward with the blocks its own plan gives the shape: ONE
    kernel for dq and one for dk/dv a call (the benchmark's rooflines
    count the device operations of each name), the other side of a head
    resident or in major blocks, within the VMEM the plan asks for."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A

    plan = A.bwd_block_plan(seq, seq, head_dim, causal)
    resident = min(seq, 4096 * 128 // head_dim)
    assert (plan.block_k_major, plan.block_q_major) == (resident, resident)
    assert max(plan.dq_vmem_bytes, plan.dkdv_vmem_bytes) < 16 * 2 ** 20
    x = _struct((batch, seq, heads, head_dim), jnp.bfloat16, one_chip)
    lse = _struct((batch, heads, seq), jnp.float32, one_chip)
    bwd = jax.jit(lambda q, k, v, out, lse, dout: A._pallas_bwd(
        q, k, v, out, lse, dout, causal, head_dim ** -0.5))
    # delta by its kernel where the kernels index the lanes
    want = {"flash_bwd_dq": 1, "flash_bwd_dkdv": 1}
    if head_dim % 128 == 0:
        want["attn_delta"] = 1
    assert _kernels(bwd.lower(x, x, x, x, lse, x).compile()) == want


def test_windowed_flash_kernels_compile(one_chip):
    """The cell mellum2_l8_train_s8192's windowed call (S 8192, W 1024):
    the forward and both backward kernels under their own names, the band
    of 45 of a head's 136 sub-blocks, within the VMEM the plans ask for."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A

    batch, seq, heads, head_dim, window = 4, 8192, 32, 128, 1024
    fwd_plan = A.fwd_block_plan(seq, seq, head_dim, True, window=window)
    bwd_plan = A.bwd_block_plan(seq, seq, head_dim, True, window=window)
    for plan in (fwd_plan, bwd_plan):
        assert (plan.unmasked, plan.masked, plan.edge) == (15, 16, 14)
    assert max(fwd_plan.vmem_bytes, bwd_plan.dq_vmem_bytes,
               bwd_plan.dkdv_vmem_bytes) < 16 * 2 ** 20
    x = _struct((batch, seq, heads, head_dim), jnp.bfloat16, one_chip)
    lse = _struct((batch, heads, seq), jnp.float32, one_chip)
    fwd = jax.jit(lambda q, k, v: A._pallas_fwd(
        q, k, v, True, head_dim ** -0.5, fwd_plan))
    text = fwd.lower(x, x, x).compile().as_text()
    assert "swa_fwd" in text and "flash_fwd" not in text
    bwd = jax.jit(lambda q, k, v, out, lse, dout: A._pallas_bwd(
        q, k, v, out, lse, dout, True, head_dim ** -0.5, bwd_plan))
    text = bwd.lower(x, x, x, x, lse, x).compile().as_text()
    assert "swa_bwd_dq" in text and "swa_bwd_dkdv" in text
    assert "flash_bwd" not in text


@pytest.mark.parametrize("batch,seq,heads,head_dim,rope_dim", [
    (4, 4096, 32, 128, 128),   # q of the Mistral cells; k at 8 heads below
    (4, 4096, 8, 128, 128),
    (2, 8192, 20, 256, 64),    # q of glm47flash_l7_train_s8192
])
def test_rope_on_the_lanes_compiles(one_chip, pallas_tier, batch, seq, heads,
                                    head_dim, rope_dim):
    """``ops.layers.rope_lanes``: the kernel that rotates a head's last
    tile of lanes in place, forward and (the sines negated) backward."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import layers as L

    assert L.rope_tier(seq, head_dim, rope_dim)
    x = _struct((batch, seq, heads * head_dim), jnp.bfloat16, one_chip)
    table = _struct((seq, rope_dim // 2), jnp.float32, one_chip)

    def loss(x, cos, sin):
        return L.rope_lanes(x, cos, sin, heads, rope_dim).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss)).lower(x, table, table).compile()
    assert _kernels(compiled) == {"rope_lanes": 1}  # the forward's is dead


def _tick_args(one_chip):
    import jax.numpy as jnp

    matrix = _struct((N_NODES, N_RES), jnp.float32, one_chip)
    return dict(
        matrix=matrix,
        reqs=_struct((N_CLASSES, N_RES), jnp.float32, one_chip),
        ks=_struct((N_CLASSES,), jnp.float32, one_chip),
        alive=_struct((N_NODES,), jnp.bool_, one_chip))


def test_fused_tick_compiles(one_chip):
    from ray_tpu.scheduler.policy import BatchedHybridPolicy

    a = _tick_args(one_chip)
    tick = BatchedHybridPolicy(use_jax=True)._build_jax_fused()
    compiled = tick.lower(a["reqs"], a["ks"], a["matrix"], a["matrix"],
                          a["alive"], 0, 0.5).compile()
    assert compiled.out_info.shape == (N_CLASSES, N_NODES)
    assert compiled.out_info.dtype == np.int32
    assert compiled.output_shardings.device_set == one_chip.device_set


def test_pipelined_step_compiles(one_chip):
    from ray_tpu.scheduler.policy import BatchedHybridPolicy

    a = _tick_args(one_chip)
    m = a["matrix"]
    step = BatchedHybridPolicy(use_jax=True)._build_jax_pipelined_step()
    compiled = step.lower(m, m, m, a["reqs"], a["ks"], m, a["alive"], 0,
                          0.5).compile()
    avail, usage, counts = compiled.out_info
    assert avail.shape == usage.shape == (N_NODES, N_RES)
    assert counts.shape == (N_CLASSES, N_NODES)
    # the availability buffer is donated: updated in place on the device
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        N_NODES * N_RES * 4)


def test_mirror_row_scatter_compiles(one_chip):
    import jax.numpy as jnp

    from ray_tpu.scheduler.policy import DeviceMatrixMirror

    m = _tick_args(one_chip)["matrix"]
    rows = _struct((16, N_RES), jnp.float32, one_chip)
    idx = _struct((16,), jnp.int32, one_chip)
    compiled = DeviceMatrixMirror._build_set_rows().lower(
        m, m, idx, rows, rows).compile()
    total, avail = compiled.out_info
    assert total.shape == avail.shape == (N_NODES, N_RES)


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


FLASH_UNDER_FULL_REMAT = {"flash_fwd": 2, "flash_bwd_dq": 1,
                          "flash_bwd_dkdv": 1, "attn_delta": 1}
# q and k rotated in the forward, its recompute and the backward
ROPE_UNDER_FULL_REMAT = {"rope_lanes": 6}
# a shard keeps the heads-major copy and XLA's delta and rotation
FLASH_ON_A_MESH = {"flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkdv": 1}


def test_dense_train_step_compiles(topo, pallas_tier):
    """The 632 M dense model's step at full width, depth cut to 2 layers
    (the layer scan makes the program the same at 12), at the smoke's
    batch: fits one chip, with the forward and both backward kernels."""
    import chip_smoke

    from ray_tpu.models.training import build_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(), topo.devices[:1])
    step, init_fn = build_train_step(chip_smoke.dense_config(2), mesh)
    params, opt_state = _abstract_train_state(init_fn)
    compiled = step.lower(params, opt_state,
                          _tokens(mesh, chip_smoke.BATCH)).compile()
    assert _kernels(compiled) == {**FLASH_UNDER_FULL_REMAT,
                                  **ROPE_UNDER_FULL_REMAT}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_hybrid_train_step_compiles(topo, pallas_tier):
    """The Nemotron-H period MEMEMEM*E at the widths of the cell
    nemotron_twotower_l9_train_s8192 (16 of 128 experts held, an eighth
    of the vocabulary; 986.3 M parameters), the cell's rows x 8192 tokens
    under the configuration's optimizer (warm-up, carried rounding): fits
    one chip, the attention layer takes the flash kernels (two K/V major
    blocks at 8192 keys), the experts' grouped products the megablox
    kernels (ops/grouped.py)."""
    import json

    import jax

    from benchmark.drivers.hybrid_train_steps import model_config
    from ray_tpu.models.training import build_train_step, make_optimizer
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/nemotron_twotower_30b_l9_ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            root, "benchmark/workloads/nemotron_twotower_l9_train_s8192.json"
            )) as f:
        rows = json.load(f)["batch"]
    hp = config["run"]["optimizer"]
    mesh = build_mesh(MeshSpec(), topo.devices[:1])
    step, init_fn = build_train_step(
        model_config(config, 8192), mesh, optimizer=make_optimizer(
            learning_rate=hp["learning_rate"],
            weight_decay=hp["weight_decay"], b1=hp["b1"], b2=hp["b2"],
            grad_clip=hp["grad_clip"], warmup_steps=hp["warmup_steps"],
            carry=hp["carry_rounding"]))
    params, opt_state = _abstract_train_state(init_fn)
    assert sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(params)) == 986_254_848
    compiled = step.lower(params, opt_state,
                          _tokens(mesh, rows, 8192)).compile()
    kernels = _kernels(compiled)
    text = compiled.as_text()
    # the Mamba layers' scan: the forward kernel in the first pass (once
    # where the four layers share their code, else one a layer), one a
    # layer where the backward rebuilds the layer (it keeps the states),
    # one backward kernel a layer, each under the scope the ``ssd_*``
    # metrics read
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]

    def kernels_under(scope):
        return lambda name, *path: sum(all(part in line for part in path + (
            f"/mamba/{scope}/", f"/{name}/pallas_call")) for line in calls)

    scan_kernels = kernels_under("ssd")
    first_pass = scan_kernels("ssd_fwd", "jit(train_step)/jvp(layers)")
    assert first_pass in (1, 4)
    assert scan_kernels("ssd_fwd", "transpose(jvp(layers))",
                        "rematted_computation") == 4
    assert scan_kernels("ssd_bwd", "transpose(jvp(layers))") == 4
    assert scan_kernels("ssd_bwd", "rematted_computation") == 0

    # the convolution in front of the scan (ops/ssd.py::conv_silu): a
    # kernel a piece (x, B, C: cut at lanes 4096 and 5120), in each
    # layer's first pass and again where the backward rebuilds the layer,
    # and one backward kernel a piece and layer; nothing under the scope
    # pads the sequence by K - 1 any more (the jnp form's four taps)
    conv_kernels = kernels_under("conv")
    first_conv = conv_kernels("conv_fwd", "jit(train_step)/jvp(layers)")
    assert first_conv in (3, 12)
    assert conv_kernels("conv_fwd", "transpose(jvp(layers))",
                        "rematted_computation") == 12
    assert conv_kernels("conv_bwd", "transpose(jvp(layers))") == 12
    assert conv_kernels("conv_bwd", "rematted_computation") == 0
    assert "8195,6144]" not in text
    # they read their lanes of the projection's output where it lies: no
    # slice of it is left, and XLA's own rematerialisation, which rebuilt
    # the projection 11 times in the backward for its slices (PR 34's
    # compile), does so once a layer and recompute
    assert "bf16[4,8192,6144]" not in text
    rebuilt = [line for line in text.splitlines()
               if "kind=kOutput" in line and "rematted_computation" in line
               and "/mamba/in_proj/" in line and "dot_general" in line]
    # the gate and the norm behind the scan (ops/ssd.py::gated_group_norm):
    # one forward kernel a layer in the first pass (or one where the
    # layers share their code) and one where the backward rebuilds the
    # layer, one backward kernel a layer, under the scope the anatomy and
    # ``mamba_gate_norm_time_share`` read; the float32 product y * silu(z)
    # that the jnp form wrote to HBM three times is in no buffer, and the
    # gate is read where the projection wrote it: no slice of the
    # projection's output is made for it
    gate_norm_kernels = kernels_under("gate_norm")
    first_norm = gate_norm_kernels("gate_norm_fwd",
                                   "jit(train_step)/jvp(layers)")
    assert first_norm in (1, 4)
    assert gate_norm_kernels("gate_norm_fwd", "transpose(jvp(layers))",
                             "rematted_computation") == 4
    assert gate_norm_kernels("gate_norm_bwd", "transpose(jvp(layers))") == 4
    assert gate_norm_kernels("gate_norm_bwd", "rematted_computation") == 0
    assert "f32[4,8192,4096]" not in text
    assert not [line for line in text.splitlines() if " slice(" in line
                and "= bf16[4,8192,4096]" in line]
    # with the float32 temporaries gone XLA's rematerialisation rebuilds
    # the projection once a layer, for the checkpoint's recompute, and no
    # more (8 before: PR 35's compile)
    assert len(rebuilt) <= 4
    # the rows' way back to the tokens (ops/grouped.py): one kernel a
    # layer under ``combine`` forwards (the recomputed one feeds nothing
    # and is dropped), one under ``dispatch`` as that gather's transpose
    moved = [line for line in calls if "/rows_added/pallas_call" in line]
    assert sum("jvp(layers)" in line and "/moe/combine/" in line
               and "transpose(" not in line for line in moved) == 4
    assert sum("transpose(jvp(layers))" in line and "/moe/dispatch/" in line
               for line in moved) == 4
    # the grouped products of 4 layers: two forward, two recomputed and
    # the four of their gradients (gmm for the rows, tgmm for the banks)
    assert kernels.pop("other") - first_pass - 8 - len(moved) \
        - first_conv - 24 - first_norm - 8 == 4 * 8
    assert len(moved) == 8 and "gmm" in text
    assert kernels == FLASH_UNDER_FULL_REMAT
    # the carried rounding is still there after the TPU compiler's
    # fusions (a conversion there and back is not: excess precision)
    assert "reduce-precision(" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    # no more workspace than the scan as a lax.scan of jnp chunks took
    # (PR 28's compile of this step: 8 967 446 528 B; the step fits a
    # chip by 0.04 GB with that)
    assert mem.temp_size_in_bytes <= 8_967_446_528
    # nor than before dispatch and combine followed the draw (PR 30's
    # compile: 8 312 899 584 B; 8 309 819 392 since)
    assert mem.temp_size_in_bytes <= 8_312_899_584
    # nor than while the convolution padded, widened and added in HBM
    # (PR 34's compile with this test's optimizer: 8 148 050 432 B;
    # 7 607 191 040 since the kernels), and the most that is live at once
    # 14 933 460 992 B where it was 15 533 466 624
    assert mem.temp_size_in_bytes <= 7_650_000_000
    assert mem.peak_memory_in_bytes <= 15_000_000_000
    # nor than while the gate and the norm went through float32 in HBM
    # (PR 36's compile: 7 607 191 040 B and 14 933 460 992 live;
    # 6 837 018 112 and 14 459 006 464 since the kernels, PR 38)
    assert mem.temp_size_in_bytes <= 6_900_000_000
    assert mem.peak_memory_in_bytes <= 14_500_000_000


def test_mellum_train_step_compiles(topo, pallas_tier):
    """Two periods sliding, sliding, sliding, full of Mellum 2 at the
    widths of the cell mellum2_l8_train_s8192 (16 of 64 experts held, a
    quarter of the vocabulary; 1077.1 M parameters), the cell's rows x
    8192 tokens under the configuration's optimizer: fits one chip (as a
    loop over the two periods it does not: 16.63 GB of 15.75), the
    sliding layers take the windowed kernels and the full layers the
    causal ones, the experts' grouped products the megablox kernels."""
    import json

    import jax

    from benchmark.drivers.mellum_train_steps import model_config
    from ray_tpu.models.training import build_train_step, make_optimizer
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/mellum2_12b_l8_ep4.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            root, "benchmark/workloads/mellum2_l8_train_s8192.json")) as f:
        rows = json.load(f)["batch"]
    hp = config["run"]["optimizer"]
    mesh = build_mesh(MeshSpec(), topo.devices[:1])
    step, init_fn = build_train_step(
        model_config(config, 8192), mesh, optimizer=make_optimizer(
            learning_rate=hp["learning_rate"],
            weight_decay=hp["weight_decay"], b1=hp["b1"], b2=hp["b2"],
            grad_clip=hp["grad_clip"], warmup_steps=hp["warmup_steps"],
            carry=hp["carry_rounding"]))
    params, opt_state = _abstract_train_state(init_fn)
    assert sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(params)) == 1_077_057_792
    compiled = step.lower(params, opt_state,
                          _tokens(mesh, rows, 8192)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]

    def named(name):
        return sum(f"{name})" in line or f"/{name}/" in line
                   for line in calls)

    # two periods run unrolled (transformer.UNROLLED_PERIODS): six
    # sliding layers and two full ones; under full remat each layer's
    # forward kernel is in the program twice
    assert (named("swa_fwd"), named("swa_bwd_dq"), named("swa_bwd_dkdv")
            ) == (12, 6, 6)
    kernels = _kernels(compiled)
    # delta once a layer's backward, windowed or full; q and k rotated in
    # each layer's three passes
    assert {k: kernels[k] for k in FLASH_UNDER_FULL_REMAT} == {
        "flash_fwd": 4, "flash_bwd_dq": 2, "flash_bwd_dkdv": 2,
        "attn_delta": 8}
    assert kernels["rope_lanes"] == 8 * ROPE_UNDER_FULL_REMAT["rope_lanes"]
    assert "gmm" in text and "reduce-precision(" in text
    # eight expert layers' rows back to the tokens, forwards and as the
    # dispatch's transpose
    assert named("rows_added") == 16
    mem = compiled.memory_analysis()
    print("mellum step memory_analysis:", mem.argument_size_in_bytes,
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    # no more workspace than before dispatch and combine followed the
    # draw (PR 30's compile: 7 005 763 584 B; 6 732 486 144 since: the
    # combine's transpose writes into the buffer it reads)
    assert mem.temp_size_in_bytes <= 7_005_763_584


def test_glm_train_step_compiles(topo, pallas_tier):
    """Layer 0 and six periods of latent attention and experts of
    GLM-4.7-Flash with its MTP module, at the widths of the cell
    glm47flash_l7_train_s8192 (8 of 64 experts held, an eighth of the
    vocabulary; 920.2 M parameters), the cell's rows x 8192 tokens under
    the configuration's optimizer: fits one chip, the six periods a loop
    (transformer.UNROLLED_PERIODS), so the program spells out three
    latent blocks (layer 0's, a period's, the module's), each with the
    three flash kernels at heads of 256, and two expert layers, their
    grouped products the megablox kernels."""
    import json

    import jax

    from benchmark.drivers.glm_train_steps import model_config
    from ray_tpu.models.training import build_train_step, make_optimizer
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/glm47_flash_l7_ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            root, "benchmark/workloads/glm47flash_l7_train_s8192.json")) as f:
        rows = json.load(f)["batch"]
    hp = config["run"]["optimizer"]
    mesh = build_mesh(MeshSpec(), topo.devices[:1])
    step, init_fn = build_train_step(
        model_config(config, 8192), mesh, optimizer=make_optimizer(
            learning_rate=hp["learning_rate"],
            weight_decay=hp["weight_decay"], b1=hp["b1"], b2=hp["b2"],
            grad_clip=hp["grad_clip"], warmup_steps=hp["warmup_steps"],
            carry=hp["carry_rounding"]))
    params, opt_state = _abstract_train_state(init_fn)
    assert sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(params)) == 920_177_088
    compiled = step.lower(params, opt_state,
                          _tokens(mesh, rows, 8192)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]

    def named(name, *path):
        return sum((f"{name})" in line or f"/{name}/" in line)
                   and all(part in line for part in path) for line in calls)

    # three latent blocks in the program's text, each forward kernel twice
    # under full remat; the module's block among them, under its own scope
    kernels = _kernels(compiled)
    assert {k: kernels[k] for k in FLASH_UNDER_FULL_REMAT} == {
        "flash_fwd": 6, "flash_bwd_dq": 3, "flash_bwd_dkdv": 3,
        "attn_delta": 3}
    # q's last lanes rotated in place in each block's three passes; no
    # array between the up-projections and the kernels is laid S-minor or
    # turned (the 7.4 % of the step that PR 33 took out)
    assert kernels["rope_lanes"] == 9
    assert (named("flash_fwd", "jvp(mtp)", "/attention/flash/"),
            named("flash_bwd_dq", "jvp(mtp)", "/attention/flash/")) == (2, 1)
    assert "gmm" in text and "reduce-precision(" in text
    # a period's and the module's expert layers' rows back to the tokens,
    # forwards and as the dispatch's transpose
    assert named("rows_added") == 4
    assert named("rows_added", "jvp(mtp)", "/mlp/moe/") == 2
    mem = compiled.memory_analysis()
    print("glm step memory_analysis:", mem.argument_size_in_bytes,
          mem.temp_size_in_bytes, mem.peak_memory_in_bytes)
    # arguments + temp under the chip's bytes_limit (7.37 + 9.07 GB of
    # 16.91, with a row buffer of three times the even draw a layer).
    # ``temp`` is the extent of the heap the temporaries are packed into;
    # ``peak_memory`` is the most that is live at once, arguments
    # included. PR 33 took 0.19 GB off the second (13.35 -> 13.15 GB: the
    # transposed copies are no longer live, and no buffer of the step
    # grew) while the packing left the first 0.12 GB longer (8.96 ->
    # 9.07; PERF.md section 6, PR 33), so each has its own bound: the
    # step before PR 33 does not pass the second
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    assert mem.temp_size_in_bytes <= 9_100_000_000
    assert mem.peak_memory_in_bytes <= 13_200_000_000


def test_nemotron3_train_step_compiles_and_says_what_fits(topo, pallas_tier):
    """The period MEMEMEM*E of Nemotron-3-Super with its *E module, at the
    widths of the cell nemotron3super_l9_train_s8192 (8 of 512 experts
    held in a latent of 1024, an eighth of the vocabulary; 1170.5 M
    parameters), the cell's rows x 8192 tokens under the configuration's
    optimizer: fits one chip, twice the rows do not (which is why the cell
    has the rows it has); the Mamba layers take the scan's and the
    convolution's kernels at 16 heads a group, both attention layers
    (the stack's, the module's) the flash kernels, the experts' products
    the megablox kernels at the latent's width, and the rows come back
    through ``rows_added`` at 1024 lanes."""
    import json

    import jax

    from benchmark.drivers.nemotron3_train_steps import model_config
    from ray_tpu.models.training import build_train_step, make_optimizer
    from ray_tpu.observability.metrics import (
        mamba_conv_calls,
        mamba_gate_norm_calls,
        moe_latent_proj_calls,
        ssd_scan_chunks,
    )
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/nemotron3_super_l9_ep64.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            root, "benchmark/workloads/"
            "nemotron3super_l9_train_s8192.json")) as f:
        rows = json.load(f)["batch"]
    hp = config["run"]["optimizer"]
    mesh = build_mesh(MeshSpec(), topo.devices[:1])
    step, init_fn = build_train_step(
        model_config(config, 8192), mesh, optimizer=make_optimizer(
            learning_rate=hp["learning_rate"],
            weight_decay=hp["weight_decay"], b1=hp["b1"], b2=hp["b2"],
            grad_clip=hp["grad_clip"], warmup_steps=hp["warmup_steps"],
            carry=hp["carry_rounding"]))
    params, opt_state = _abstract_train_state(init_fn)
    assert sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(params)) == 1_170_513_920
    counters = (ssd_scan_chunks, mamba_conv_calls, moe_latent_proj_calls,
                mamba_gate_norm_calls)
    before = [dict(c.series()) for c in counters]
    compiled = step.lower(params, opt_state,
                          _tokens(mesh, rows, 8192)).compile()
    scans, convs, maps, norms = (
        {k: v - was.get(k, 0) for k, v in c.series().items()
         if v != was.get(k, 0)} for c, was in zip(counters, before))
    # the kernel tier alone, at 16 heads a group
    assert set(scans) == {("kernel", "fwd"), ("kernel", "bwd")}
    assert set(convs) == {("kernel", "fwd"), ("kernel", "bwd")}
    assert set(norms) == {("kernel", "fwd"), ("kernel", "bwd")}
    assert set(maps) == {("in",), ("out",)} and maps[("in",)] == maps[
        ("out",)]
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]

    def named(name, *path):
        return sum((f"{name})" in line or f"/{name}/" in line)
                   and all(part in line for part in path) for line in calls)

    # two attention layers in the program's text (the period is spelled
    # out: one period is under UNROLLED_PERIODS), no rotary kernel
    kernels = _kernels(compiled)
    assert {k: kernels[k] for k in FLASH_UNDER_FULL_REMAT} == {
        "flash_fwd": 4, "flash_bwd_dq": 2, "flash_bwd_dkdv": 2,
        "attn_delta": 2}
    assert "rope_lanes" not in kernels
    assert (named("flash_fwd", "jvp(mtp)", "/attention/flash/"),
            named("flash_bwd_dq", "jvp(mtp)", "/attention/flash/")) == (2, 1)
    # four Mamba layers: the scan forward, recomputed and backward, the
    # convolution's three pieces each way
    assert (named("ssd_fwd"), named("ssd_bwd")) == (8, 4)
    assert (named("conv_fwd"), named("conv_bwd")) == (24, 12)
    # the gate and the norm behind the scan, a group of 1024 lanes a
    # block: forwards in the first pass and in the recompute, one backward
    # kernel a layer and none in the recompute; the float32 product that
    # the jnp form wrote to HBM is nowhere under the scope, no slice of
    # the projection's output is made for the gate, and the projection is
    # rebuilt once a layer, for the checkpoint's recompute, and no more
    gate_norm = ("/mamba/gate_norm/",)
    assert named("gate_norm_fwd", "jit(train_step)/jvp(layers)",
                 *gate_norm) == 4
    assert named("gate_norm_fwd", "transpose(jvp(layers))",
                 "rematted_computation", *gate_norm) == 4
    assert named("gate_norm_bwd", "transpose(jvp(layers))", *gate_norm) == 4
    assert named("gate_norm_bwd", "rematted_computation") == 0
    assert not [line for line in text.splitlines()
                if "f32[2,8192,8192]" in line and gate_norm[0] in line]
    assert not [line for line in text.splitlines() if " slice(" in line
                and "= bf16[2,8192,8192]" in line]
    rebuilt = [line for line in text.splitlines()
               if "kind=kOutput" in line and "rematted_computation" in line
               and "/mamba/in_proj/" in line and "dot_general" in line]
    assert len(rebuilt) == 4
    # five expert layers' rows back to the tokens: forwards, again in
    # the recompute (the map back up reads the combined latent, so its
    # weights' gradient needs it: the stacks without a latent add the
    # combine to the residual and never rebuild it) and as the dispatch's
    # transpose; the module's among them
    assert named("rows_added") == 15
    assert named("rows_added", "jvp(mtp)", "/mlp/moe/") == 3
    assert "gmm" in text and "reduce-precision(" in text
    mem = compiled.memory_analysis()
    print("nemotron3 step memory_analysis:", mem.argument_size_in_bytes,
          mem.temp_size_in_bytes, mem.peak_memory_in_bytes)
    # arguments + temp under the chip's bytes_limit: 9.45 + 7.09 GB of
    # 16.91 at 2 rows (1 row: 9.45 + 4.95)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    assert mem.temp_size_in_bytes <= 7_200_000_000
    # 9.45 + 6.14 GB and 15.49 GB live at once since the gate and the norm
    # are a kernel pair (PR 38's compile: 6 135 038 464 and
    # 15 489 353 728 B)
    assert mem.temp_size_in_bytes <= 6_200_000_000
    assert mem.peak_memory_in_bytes <= 15_550_000_000
    # twice the rows: the chip's compiler refuses the program (19.75 GiB)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|memory"):
        step.lower(params, opt_state,
                   _tokens(mesh, 2 * rows, 8192)).compile()


@pytest.mark.parametrize("case,seq,kernels,collective", [
    # ring attention is plain jnp in a shard_map over the whole mesh
    ("gspmd dense dp2(fsdp) x sp2(ring)", S, {}, "collective-permute"),
    ("gspmd dense dp2(fsdp) x tp2", S, FLASH_ON_A_MESH, "all-gather"),
    ("pipeline pp2 x tp2", S, FLASH_ON_A_MESH, "collective-permute"),
    # a sequence that does not tile takes the blockwise tier, which must
    # stay out of the nested shard_map: under remat the partitioner
    # refuses it there ("manual axes come before free axes")
    ("pipeline pp2 x tp2", 1000, {}, "collective-permute"),
])
def test_sharded_step_compiles_on_four_chips(four_chip_step, case, seq,
                                             kernels, collective):
    """The flash-kernel steps of chip_smoke.py --chips 4 over the 2x2
    mesh: each kernel sits in a shard_map of its own (nested in the
    pipeline's), the only way the chip's compiler takes one on a mesh."""
    compiled = four_chip_step(case, seq)
    assert _kernels(compiled) == kernels
    assert collective in compiled.as_text()


@pytest.fixture(scope="module")
def _four_chip_steps():
    return {}


@pytest.fixture
def four_chip_step(topo, pallas_tier, _four_chip_steps):
    """A case of ``chip_smoke.four_chip_cases()`` compiled for the 2x2
    mesh, once a module: -> compiled(case, seq)."""
    import chip_smoke

    from ray_tpu.parallel.mesh import build_mesh

    def compiled(case, seq):
        if (case, seq) not in _four_chip_steps:
            (spec, build), = [(spec, build) for name, spec, build
                              in chip_smoke.four_chip_cases() if name == case]
            mesh = build_mesh(spec, topo.devices)
            step, init_fn, batch = build(mesh)
            params, opt_state = _abstract_train_state(init_fn)
            _four_chip_steps[case, seq] = step.lower(
                params, opt_state, _tokens(mesh, batch, seq)).compile()
        return _four_chip_steps[case, seq]

    return compiled


def test_four_chip_loss_keeps_the_logits_on_their_chip(four_chip_step):
    """The loss over dp2(fsdp) x tp2 (``transformer._mean_nll_on_mesh``):
    no collective inside the chunk loops of the loss (forward, and the
    backward's with its recompute) carries more than a chunk's rows'
    float32 sums, ``[rows, chunk]``; the parent's loss under GSPMD,
    compiled here, all-reduced a chunk's partial logits ``[rows, chunk,
    V/tp]`` over dp there (on the chip: gathered the unembedding and
    all-reduced its float32 gradient a chunk). The unembedding is
    gathered over dp once, outside them; its gradient reduce-scattered
    once."""
    import chip_smoke

    text = four_chip_step("gspmd dense dp2(fsdp) x tp2", S).as_text()
    comps = _hlo_computations(text)
    loops = [line for lines in comps.values() for line in lines
             if " while(" in line and _LOSS_SCOPE.search(line)]
    assert len(loops) == 2     # the forward's chunks; the backward's
    inside = _called_from(comps, [_callee(line, "body=") for line in loops])
    rows, chunk = 4, 256
    widths = chip_smoke.WIDTHS
    vocab, hidden = widths["vocab_size"], widths["hidden"]
    unembed = f"[{hidden},{vocab // 2}]"
    in_loops = [line for name in inside for line in comps[name]
                if _COLLECTIVE.search(line)]
    assert in_loops     # the log-sum-exp's max and sums over tp
    for line in in_loops:
        assert _largest_result(line) <= rows * chunk, line
    gathers = [line for lines in comps.values() for line in lines
               if " all-gather(" in line
               and line.split("=", 1)[1].lstrip().startswith(
                   f"bf16{unembed}")]
    assert len(gathers) == 1
    assert not [line for name in inside for line in comps[name]
                if line in gathers]
    assert len([line for lines in comps.values() for line in lines
                if " reduce-scatter(" in line
                and f"bf16[{hidden // 2},{vocab // 2}]" in line]) == 1


_LOSS_SCOPE = re.compile(r'op_name="[^"]*[/(]loss[)/][^"]*"')
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


def test_graft_entry_forward_compiles(one_chip, pallas_tier):
    """`entry()`'s forward at its own shapes (head_dim 32, S=128)."""
    import jax

    import __graft_entry__ as graft

    fwd, args = graft.entry()
    args = jax.tree.map(lambda x: _struct(x.shape, x.dtype, one_chip), args)
    assert _kernels(fwd.lower(*args).compile()) == {"flash_fwd": 1}


@pytest.mark.parametrize("n,name,kernels", [
    (4, "gspmd", {}),    # sp2: ring attention, its own blockwise math
    (4, "fsdp", {}),
    # S=32 is one tile, so the forward kernel runs; head_dim 16 sends
    # the backward to the blockwise tier, outside any shard_map: nested
    # in the pipeline's region, and under plain jit over tp2
    (4, "pipeline", {"flash_fwd": 1}),
    (2, "fsdp", {"flash_fwd": 1}),
])
def test_dryrun_step_compiles(topo, pallas_tier, n, name, kernels):
    """`python __graft_entry__.py` on n chips: its tiny shapes take
    another tier per shape than the real widths do."""
    import __graft_entry__ as graft

    from ray_tpu.parallel.mesh import build_mesh

    (spec, cfg, build), = [(spec, cfg, build) for step_name, spec, cfg, build
                           in graft.dryrun_steps(n) if step_name == name]
    mesh = build_mesh(spec, topo.devices[:n])
    step, init_fn = build(cfg, mesh)
    params, opt_state = _abstract_train_state(init_fn)
    tokens = _tokens(mesh, max(2, 2 * spec.dp), seq=32)
    compiled = step.lower(params, opt_state, tokens).compile()
    assert _kernels(compiled) == kernels
