"""The step of the cell glm47flash_l7_train_s8192 compiled for a
described v5e (``conftest.py``)."""

from conftest import (
    FLASH_UNDER_FULL_REMAT,
    _held,
    _kernels,
    _named,
    _router,
    cell_step,
)


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
    from benchmark.drivers.glm_train_steps import model_config

    step, (params, opt_state), tokens = cell_step(
        topo, "glm47flash_l7_train_s8192", model_config)
    assert _held(params) == 920_177_088
    compiled = step.lower(params, opt_state, tokens).compile()
    text = compiled.as_text()

    def named(name, *path):
        return _named(text, name, *path)

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
    # the routers' choice (a period's, the module's): a kernel in each
    # expert layer's first pass and its recompute, and nothing under the
    # scope sorts, gathers or scatters (ops/router.py)
    assert _router(text) == (4, [])
    assert named("router_topk_fwd", "jvp(mtp)", "/moe/router/") == 2
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
