"""The step of the cell nemotron_twotower_l9_train_s8192 compiled for a
described v5e (``conftest.py``)."""

from conftest import (
    FLASH_UNDER_FULL_REMAT,
    _held,
    _kernels,
    _router,
    cell_step,
)


def test_hybrid_train_step_compiles(topo, pallas_tier):
    """The Nemotron-H period MEMEMEM*E at the widths of the cell
    nemotron_twotower_l9_train_s8192 (16 of 128 experts held, an eighth
    of the vocabulary; 986.3 M parameters), the cell's rows x 8192 tokens
    under the configuration's optimizer (warm-up, carried rounding): fits
    one chip, the attention layer takes the flash kernels (two K/V major
    blocks at 8192 keys), the experts' grouped products the megablox
    kernels (ops/grouped.py)."""
    from benchmark.drivers.hybrid_train_steps import model_config

    step, (params, opt_state), tokens = cell_step(
        topo, "nemotron_twotower_l9_train_s8192", model_config)
    assert _held(params) == 986_254_848
    compiled = step.lower(params, opt_state, tokens).compile()
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
    # the routers' choice: a kernel in each expert layer's first pass and
    # its recompute, and nothing under the scope sorts, gathers or
    # scatters (ops/router.py)
    chose, left = _router(text)
    assert (chose, left) == (8, [])
    # the grouped products of 4 layers: two forward, two recomputed and
    # the four of their gradients (gmm for the rows, tgmm for the banks)
    assert kernels.pop("other") - first_pass - 8 - len(moved) \
        - first_conv - 24 - first_norm - 8 - chose == 4 * 8
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
