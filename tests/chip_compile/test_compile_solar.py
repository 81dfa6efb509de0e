"""The Solar-Open2 cell's step compiled for a described v5e
(``conftest.py``). Two sizes: one delta-rule layer with its expert layer
at the cell's widths and row, which tier-1 runs (a later change to
``kda_block`` or ``ops/kda.py`` that widens what a layer holds shows
here, where the whole step has 5 % of the chip left); and the cell's
whole step, marked ``slow``: its hundred seconds of a many-threaded
compile stay out of tier-1 (``python3 -m pytest
tests/chip_compile/test_compile_solar.py -m slow``)."""

import pytest
from benchmark import flops_solar
from conftest import (
    _counted,
    _held,
    _kernels,
    _named,
    _router,
    cell_config,
    cell_step,
)

WORKLOAD = "solaropen2_l4_train_1row"
# the cell's configuration cut to one K layer and its expert layer
ONE_LAYER = dict(num_hidden_layers=1, gqa_layers=[])

# what the one-layer step needs beyond its arguments today (this file's
# compile for a described v5e, PR 40: of it 0.96 GB the gradients; 2.58 GB
# with a chunk's operands as XLA's arrays, PR 39), bytes
ONE_LAYER_TEMP = 2_034_930_688


def _compiled_step(topo, **overrides):
    """(the step of the cell's configuration with ``overrides`` at its
    one row of 8192 compiled for one described chip under the
    configuration's optimizer, the parameters it holds); the delta rule
    and the convolution take the kernel tier alone while it is traced."""
    from benchmark.drivers.solar_train_steps import model_config
    from ray_tpu.observability.metrics import (
        kda_chunks,
        mamba_conv_calls,
        moe_router_calls,
    )

    step, (params, opt_state), tokens = cell_step(topo, WORKLOAD,
                                                  model_config, **overrides)
    assert tokens.shape == (1, 8192 + 1)
    compiled, (walks, convs, routers) = _counted(
        (kda_chunks, mamba_conv_calls, moe_router_calls),
        lambda: step.lower(params, opt_state, tokens).compile())
    # the kernel tier alone; 128 chunks a call
    assert set(walks) == set(convs) == set(routers) == {
        ("kernel", "fwd"), ("kernel", "bwd")}
    assert all(v % 128 == 0 for v in walks.values())
    return compiled, _held(params)


def test_one_delta_rule_layer_keeps_its_workspace(topo, pallas_tier):
    """One K layer and its expert layer at the cell's widths (64 heads of
    128, rank 128, 8 of 320 experts of 1280 held, an eighth of the
    vocabulary), one row of 8192: what a layer of the cell's step holds
    while it runs. The cell's whole step reads ``step_memory_share`` 94.26
    (PR 40; 94.98 in PR 39) and its peak lies in a layer's backward, so
    what this step needs beyond its arguments is held to what it needs
    today and a twentieth: wider than that, the cell no longer fits its
    chip."""
    compiled, held = _compiled_step(topo, **ONE_LAYER)
    assert held == flops_solar.solar_params(
        cell_config(WORKLOAD, **ONE_LAYER)[0])
    text = compiled.as_text()
    # the fused pair: forward in the first pass and in a group's
    # rebuilding, backward once; none of the four kernels it replaced
    assert [_named(text, kernel, "/kda/", "/delta/") for kernel in (
        "kda_chunk_fwd", "kda_chunk_bwd", "kda_walk_fwd", "kda_walk_bwd",
        "kda_scores_fwd", "kda_scores_bwd")] == [2, 1, 0, 0, 0, 0]
    # the router's choice, 8 of 320: a kernel in the expert layer's first
    # pass and its recompute, and nothing under the scope sorts, gathers
    # or scatters (ops/router.py)
    assert _router(text) == (2, [])
    mem = compiled.memory_analysis()
    print("solar one-layer step memory_analysis:",
          mem.argument_size_in_bytes, mem.temp_size_in_bytes,
          mem.peak_memory_in_bytes)
    assert mem.temp_size_in_bytes <= 1.05 * ONE_LAYER_TEMP


@pytest.mark.slow
def test_solar_train_step_compiles_and_fits_one_row(topo, pallas_tier):
    """The period G K K K of Solar-Open2 with an expert layer behind each
    mixer, at the widths of the cell solaropen2_l4_train_1row (8 of 320
    experts held, an eighth of the vocabulary; 1295.09 M parameters), one
    row of 8192 tokens under the configuration's optimizer: fits one chip
    beside 10.4 GB of donated state, since the delta-rule mixer runs a
    group of 8 heads at a time (all 64 at once the same compiler refused
    at 17.49 GB of 15.75 GiB, PR 39); the mixers take the delta rule's
    fused kernel pair and the convolution's, the attention layer the flash
    kernels, the experts' products the megablox kernels."""
    compiled, held = _compiled_step(topo)
    assert held == 1_295_087_424
    text = compiled.as_text()

    def named(name, *path):
        return _named(text, name, *path)

    # one attention layer: the forward twice under full remat, no rotary
    kernels = _kernels(compiled)
    assert {k: kernels[k] for k in ("flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkdv")} == {
        "flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkdv": 1}
    assert "rope_lanes" not in kernels
    # three mixers, each a loop over its groups of heads: the fused
    # forward in the first pass and in a group's rebuilding, the fused
    # backward once (the layer's own recompute of them is dead code: a
    # group keeps nothing but its inputs); the backward rebuilds a chunk's
    # operands itself
    assert named("kda_chunk_fwd", "/kda/", "/delta/") == 6
    assert named("kda_chunk_bwd", "/kda/", "/delta/") == 3
    assert not any(named(old) for old in (
        "kda_walk_fwd", "kda_walk_bwd", "kda_scores_fwd", "kda_scores_bwd"))
    assert (named("conv_fwd", "/kda/", "/conv/"),
            named("conv_bwd", "/kda/", "/conv/")) == (18, 9)
    assert "gmm" in text and "reduce-precision(" in text
    assert _router(text) == (8, [])
    mem = compiled.memory_analysis()
    print("solar step memory_analysis:", mem.argument_size_in_bytes,
          mem.temp_size_in_bytes, mem.peak_memory_in_bytes)
    # 10.41 GB of arguments + 5.44 GB of workspace of 16.91, 14.89 GB live
    # at the peak (PR 40's compile and the chip's own: 5 437 596 160 and
    # 14 892 972 544 B; 5.55 and 15.21 with a chunk's operands as XLA's,
    # PR 39; 6.08 and 15.60 with the decayed scores as XLA's too)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    assert mem.temp_size_in_bytes <= 5_540_000_000
    assert mem.peak_memory_in_bytes <= 15_000_000_000
