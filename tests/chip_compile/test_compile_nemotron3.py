"""The step of the cell nemotron3super_l9_train_s8192 compiled for a
described v5e (``conftest.py``): at the cell's rows in tier-1, and at
twice them, which the chip's compiler refuses, marked ``slow`` (the
cell's own run on the chip shows on every PR that its rows fit)."""

import pytest
from conftest import (
    FLASH_UNDER_FULL_REMAT,
    _counted,
    _held,
    _kernels,
    _named,
    _router,
    _tokens,
    cell_step,
)

WORKLOAD = "nemotron3super_l9_train_s8192"


def test_nemotron3_train_step_compiles(topo, pallas_tier):
    """The period MEMEMEM*E of Nemotron-3-Super with its *E module, at the
    widths of the cell nemotron3super_l9_train_s8192 (8 of 512 experts
    held in a latent of 1024, an eighth of the vocabulary; 1170.5 M
    parameters), the cell's rows x 8192 tokens under the configuration's
    optimizer: fits one chip; the Mamba layers take the scan's and the
    convolution's kernels at 16 heads a group, both attention layers
    (the stack's, the module's) the flash kernels, the experts' products
    the megablox kernels at the latent's width, and the rows come back
    through ``rows_added`` at 1024 lanes."""
    from benchmark.drivers.nemotron3_train_steps import model_config
    from ray_tpu.observability.metrics import (
        mamba_conv_calls,
        mamba_gate_norm_calls,
        moe_latent_proj_calls,
        moe_router_calls,
        ssd_scan_chunks,
    )

    step, (params, opt_state), tokens = cell_step(topo, WORKLOAD,
                                                  model_config)
    assert _held(params) == 1_170_513_920
    compiled, (scans, convs, maps, norms, routers) = _counted(
        (ssd_scan_chunks, mamba_conv_calls, moe_latent_proj_calls,
         mamba_gate_norm_calls, moe_router_calls),
        lambda: step.lower(params, opt_state, tokens).compile())
    # the kernel tier alone, at 16 heads a group
    assert set(scans) == {("kernel", "fwd"), ("kernel", "bwd")}
    assert set(convs) == {("kernel", "fwd"), ("kernel", "bwd")}
    assert set(norms) == {("kernel", "fwd"), ("kernel", "bwd")}
    assert set(routers) == {("kernel", "fwd"), ("kernel", "bwd")}
    assert set(maps) == {("in",), ("out",)} and maps[("in",)] == maps[
        ("out",)]
    text = compiled.as_text()

    def named(name, *path):
        return _named(text, name, *path)

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
    # the routers' choice, 22 of 512: a kernel in each expert layer's
    # first pass and its recompute, the module's among them, and nothing
    # under the scope sorts, gathers or scatters (ops/router.py)
    assert _router(text) == (10, [])
    assert named("router_topk_fwd", "jvp(mtp)", "/moe/router/") == 2
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


@pytest.mark.slow
def test_nemotron3_twice_the_rows_do_not_fit(topo, pallas_tier):
    """Twice the cell's rows: the chip's compiler refuses the program
    (19.75 GiB), which is why the cell has the rows it has."""
    from benchmark.drivers.nemotron3_train_steps import model_config

    step, (params, opt_state), tokens = cell_step(topo, WORKLOAD,
                                                  model_config)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|memory"):
        step.lower(params, opt_state, _tokens(
            tokens.sharding.mesh, 2 * tokens.shape[0], 8192)).compile()
