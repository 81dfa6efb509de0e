"""The kernels and the scheduler's programs at their own shapes, compiled
for a described v5e (``conftest.py``): the flash kernels, the windowed
ones, the rotation on the lanes, the gated short convolution, the scheduler's tick, its pipelined step
and the mirror's row scatter, and the graft entry's forward."""

import numpy as np
import pytest
from conftest import B, D, H, S, _kernels, _struct

N_NODES, N_RES, N_CLASSES = 256, 8, 32


@pytest.mark.parametrize("batch,seq,heads,head_dim", [
    (B, S, H, 128),       # chip_smoke's
    (B, S, H, 64),        # half the lanes: forward kernel, blockwise backward
    (4, 4096, 32, 128),   # the cell mistral7b_l4_train_s4096
    (32, 512, 32, 128),   # mistral7b_l4_train_s512
    (2, 4096, 16, 128),   # a chip of mistral7b_l12_train_s4096_4chip
    (1, 16384, 8, 128),   # longer than K/V may stay resident: major blocks
    (2, 8192, 20, 256),   # glm47flash_l7_train_s8192: heads of 192 + 64
    (4, 8192, 32, 64),    # lfm2_l9_train_s8192: heads of 64, heads-major
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


@pytest.mark.parametrize("batch,seq,hidden", [
    (4, 8192, 2048),   # the cell lfm2_l9_train_s8192
    (1, 1024, 128),    # the least the rule takes: a part of 128 lanes
])
def test_gated_short_convolution_compiles(one_chip, batch, seq, hidden):
    """``ops/short_conv.py``'s kernel pair at its own shapes: the forward
    reads B, C and x as three blocks of the projection's one array and
    writes y; the backward writes the projection's cotangent whole and
    the taps' partial sums, within the VMEM a v5e kernel may use by
    default. One kernel each, under the names the traces read."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import short_conv

    proj = _struct((batch, seq, 3 * hidden), jnp.bfloat16, one_chip)
    taps = _struct((3, hidden), jnp.float32, one_chip)
    dy = _struct((batch, seq, hidden), jnp.bfloat16, one_chip)
    for call, args, name in (
            (short_conv._fwd_call, (proj, taps), "short_conv_fwd"),
            (short_conv._bwd_call, (proj, taps, dy), "short_conv_bwd")):
        text = jax.jit(call).lower(*args).compile().as_text()
        kernels = [line for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line]
        assert len(kernels) == 1 and f"{name}" in kernels[0], name


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


def test_graft_entry_forward_compiles(one_chip, pallas_tier):
    """`entry()`'s forward at its own shapes (head_dim 32, S=128)."""
    import jax

    import __graft_entry__ as graft

    fwd, args = graft.entry()
    args = jax.tree.map(lambda x: _struct(x.shape, x.dtype, one_chip), args)
    assert _kernels(fwd.lower(*args).compile()) == {"flash_fwd": 1}

