"""The step of the cell mellum2_l8_train_s8192 compiled for a described
v5e (``conftest.py``)."""

from conftest import (
    FLASH_UNDER_FULL_REMAT,
    ROPE_UNDER_FULL_REMAT,
    _held,
    _kernels,
    _named,
    _router,
    cell_step,
)


def test_mellum_train_step_compiles(topo, pallas_tier):
    """Two periods sliding, sliding, sliding, full of Mellum 2 at the
    widths of the cell mellum2_l8_train_s8192 (16 of 64 experts held, a
    quarter of the vocabulary; 1077.1 M parameters), the cell's rows x
    8192 tokens under the configuration's optimizer: fits one chip (as a
    loop over the two periods it does not: 16.63 GB of 15.75), the
    sliding layers take the windowed kernels and the full layers the
    causal ones, the experts' grouped products the megablox kernels."""
    from benchmark.drivers.mellum_train_steps import model_config

    step, (params, opt_state), tokens = cell_step(
        topo, "mellum2_l8_train_s8192", model_config)
    assert _held(params) == 1_077_057_792
    compiled = step.lower(params, opt_state, tokens).compile()
    text = compiled.as_text()

    def named(name):
        return _named(text, name)

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
    # the routers' choice: a kernel in each expert layer's first pass and
    # its recompute, and nothing under the scope sorts, gathers or
    # scatters (ops/router.py)
    assert _router(text) == (16, [])
    mem = compiled.memory_analysis()
    print("mellum step memory_analysis:", mem.argument_size_in_bytes,
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    # no more workspace than before dispatch and combine followed the
    # draw (PR 30's compile: 7 005 763 584 B; 6 732 486 144 since: the
    # combine's transpose writes into the buffer it reads)
    assert mem.temp_size_in_bytes <= 7_005_763_584
