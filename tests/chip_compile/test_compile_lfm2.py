"""The LFM2 cell's step compiled for a described v5e (``conftest.py``),
cut to the lead and one period (layers 1-5: C D, then * E C E C E C E)
at the cell's 4 rows of 8192: the gated short convolution's kernel pair
in every C layer, the three flash kernels at heads of 64, the experts'
products, and what the step needs beyond its arguments. The cell's whole
step (two periods) compiles to 6.67 GB of arguments and 6.31 GB of
workspace, 12.91 GB live at the peak (a compile for a described v5e);
a full-depth guard costs minutes of tier-1 (ROADMAP C11b)."""

from benchmark import flops_lfm2
from conftest import (
    _counted,
    _held,
    _kernels,
    _named,
    _router,
    cell_config,
    cell_step,
)

WORKLOAD = "lfm2_l9_train_s8192"
# the lead and the first period of the cell's configuration
ONE_PERIOD = dict(num_hidden_layers=5,
                  layer_types=["conv", "full_attention", "conv", "conv",
                               "conv"])
# what the cut step needs beyond its arguments today (this file's compile
# for a described v5e: 3.76 GB of arguments, 7.62 GB live at the
# peak), bytes, held to it and a twentieth: a later change that widens
# what a layer holds shows here, not first on the chip
ONE_PERIOD_TEMP = 3_938_508_800


def test_the_lead_and_one_period_compile_at_the_cells_rows(topo,
                                                           pallas_tier):
    from benchmark.drivers.lfm2_train_steps import model_config
    from ray_tpu.observability.metrics import (
        moe_router_calls,
        short_conv_calls,
    )

    step, (params, opt_state), tokens = cell_step(
        topo, WORKLOAD, model_config, **ONE_PERIOD)
    assert tokens.shape == (4, 8192 + 1)
    assert _held(params) == flops_lfm2.lfm2_params(
        cell_config(WORKLOAD, **ONE_PERIOD)[0])
    compiled, (calls, routers) = _counted(
        (short_conv_calls, moe_router_calls),
        lambda: step.lower(params, opt_state, tokens).compile())
    # the kernel tier alone: the lead's C layer and the period's share one
    # function, traced outside and inside the loop
    assert calls == {("kernel", "fwd"): 2, ("kernel", "bwd"): 2}
    assert set(routers) == {("kernel", "fwd"), ("kernel", "bwd")}
    text = compiled.as_text()
    # four C layers: forward and its recompute, backward once
    assert (_named(text, "short_conv_fwd", "/short_conv/", "/gate_conv/"),
            _named(text, "short_conv_bwd", "/short_conv/", "/gate_conv/")
            ) == (8, 4)
    # one attention layer: the forward twice under full remat, the
    # backward pair at d 64 (``kernel_tiers``), the rotation by XLA
    kernels = _kernels(compiled)
    assert {k: kernels.get(k) for k in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv", "rope_lanes")} == {
        "flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkdv": 1,
        "rope_lanes": None}
    assert "gmm" in text
    # four expert layers' routers: a kernel in each one's first pass and
    # its recompute, and nothing under the scope sorts, gathers or
    # scatters (ops/router.py)
    assert _router(text) == (8, [])
    mem = compiled.memory_analysis()
    print("lfm2 lead + one period memory_analysis:",
          mem.argument_size_in_bytes, mem.temp_size_in_bytes,
          mem.peak_memory_in_bytes)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert mem.temp_size_in_bytes <= 1.05 * ONE_PERIOD_TEMP
