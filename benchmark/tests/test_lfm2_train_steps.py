"""The ``lfm2_train_steps`` driver end to end at a tiny size on the CPU,
as ``test_solar_train_steps.py`` does for the Solar-Open2 one; then the
control and this model's planted faults, each against the limit its
``why`` names, the counts, the configuration file's cut, and what a
program whose ``Stack`` knows no short-convolution kind is told."""

import dataclasses
import os

import pytest

from benchmark import (
    compare,
    compare_difference,
    flops_lfm2,
    loader,
    run as harness,
)
from benchmark.readers import mfu_from
from benchmark.tests import helpers

CELL = "lfm2_l9_train_s8192"
CONFIG = "lfm2_24b_a2b_l9_ep8"
LFM2 = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs", CONFIG + ".json"))
# the cell's layers 1-9; 4 / 2 attention heads of 16; a dense layer of
# 128; 16 experts, top-4, experts 2-4 held
TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, router_width=16, num_experts=3,
    experts_held_first=2, moe_intermediate_size=24,
    vocab_size=256)
# a float32 model, as the other rehearsals' and for their reason: at these
# widths bfloat16's rounding reads more than the control does
TINY_LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 5e-3,
               "grad_share_gap": 5e-3, "change_gap": 5e-2,
               "first_grad_diff": 5e-3}
# the limit that refuses the control and each planted fault at this size
REFUSED_BY = {
    "float8_products": "first_grad_diff", "ungated_conv": "first_grad_gap",
    "ungated_input": "first_grad_gap", "taps_reversed": "first_grad_diff",
    "no_qk_norm": "first_grad_gap", "bias_weighs": "first_grad_gap",
    "no_routed": "first_grad_gap"}


def tiny_root(tmp_path, dtype="float32"):
    root = str(tmp_path)
    spec = loader.benchmark_json(loader.ROOT)
    entry = loader.named(spec["workloads"], CELL, "workload")
    spec["workloads"] = [entry]
    spec["configs"] = [dict(loader.named(spec["configs"], entry["config"],
                                         "config"),
                            file="benchmark/configs/tiny.json")]
    helpers.write(os.path.join(root, "BENCHMARK.json"), spec)
    config = dict(LFM2, **TINY_MODEL, torch_dtype=dtype)
    config["run"] = dict(config["run"], logits_chunk=16)
    helpers.write(os.path.join(root, "benchmark/configs/tiny.json"), config)
    mix = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/workloads", CELL + ".json"))
    mix.update(batch=2, seq=32, trace_seconds=1)
    mix["check"]["limits"] = dict(TINY_LIMITS)
    helpers.write(os.path.join(root, "benchmark/workloads", CELL + ".json"),
                  mix)
    for metric in spec["per_layer"]:
        name = metric["name"] + ".json"
        helpers.write(
            os.path.join(root, "benchmark/layer_metrics", name),
            loader.read_json(os.path.join(
                loader.ROOT, "benchmark/layer_metrics", name)))
    return root, CELL


def test_run_end_to_end(tmp_path, monkeypatch, capsys):
    root, cell = tiny_root(tmp_path)
    result, out = helpers.drive(monkeypatch, capsys, root, cell,
                                seed=3_600_000_019)
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    for name, limit in TINY_LIMITS.items():
        assert 0 <= result["compared"][name]["value"] <= limit
    assert "M parameters" in out.out and "expert rows" in out.out
    assert "'moe_rows_over': 0}" in out.out.split("window:")[-1]
    # the counts' parameter total is the set-up line's
    tiny = loader.Cell(cell, root=root).config
    counted = flops_lfm2.lfm2_params(tiny)
    assert (f"({counted}; flops_lfm2 counts {counted})") in out.out


def test_traced_run_reports_what_needs_no_device(tmp_path, monkeypatch,
                                                 capsys):
    root, cell = tiny_root(tmp_path, dtype="bfloat16")
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, trace=1)
    # no TPU plane in a CPU trace: the trace's readers report nothing
    assert {"step_mfu.conv_moe", "moe_load_max_over_mean",
            "input_wait_share", "step_dispatch_ms"} <= set(result["metrics"])
    assert not {"short_conv_fwd_roofline", "short_conv_time_share",
                "short_conv_block_time_share"} & set(result["metrics"])
    assert 0 < result["metrics"]["step_mfu.conv_moe"]["value"] < 100


@pytest.mark.parametrize("what", list(REFUSED_BY))
def test_the_control_and_the_planted_faults_are_not_correct(tmp_path, what):
    """The reference with 8-bit floating operands, without the
    convolution's output gate, without its input gate, with its taps
    reversed, without the norms of q and k, with the experts weighed by
    score and bias, and without the routed experts, each put in the
    program's place: refused, and by the limit named above."""
    from benchmark.references import lfm2_decoder as reference

    root, name = tiny_root(tmp_path)
    cell = loader.Cell(name, root=root)
    driver = cell.driver()
    check = cell.workload["check"]
    assert check["control"] == "float8_products"
    assert check["faults"] == list(reference.FAULTS)
    ctx = harness.Context(cell, 1, 0.0, False)
    ref = driver.follow(ctx, keep=True)
    held = ref.pop("first_grad_leaves")
    more = dict(against=lambda name, entry, key: held[name, entry])
    broken = (driver.follow(ctx, operand=what, **more)
              if what in reference.OPERANDS
              else driver.follow(ctx, fault=what, **more))
    correct, compared = compare.judge(
        compare_difference.training_numbers(broken, ref), TINY_LIMITS)
    print(what, compared)
    assert not correct, compared
    by = compared[REFUSED_BY[what]]
    assert by["value"] > by["limit"], compared


def test_the_committed_limits_have_the_number_that_refuses_the_control():
    check = loader.Cell(CELL).workload["check"]
    assert set(check["limits"]) == set(TINY_LIMITS)
    assert set(REFUSED_BY) == {check["control"], *check["faults"]}
    assert set(check["limits"]) <= set(check["why"])


def test_a_program_whose_stack_knows_no_short_convolution_is_told_at_once(
        tmp_path, monkeypatch):
    """The parent commit's ``Stack`` has no ``short_conv_taps`` and no
    ``qk_norm``: the driver says so and exits before ``ray_tpu.init``."""
    import ray_tpu
    from ray_tpu.models import transformer as tfm

    parent = dataclasses.make_dataclass(
        "ParentStack", [(f.name, f.type, f) for f in dataclasses.fields(
            tfm.Stack) if f.name not in ("short_conv_taps", "qk_norm")],
        frozen=True)
    monkeypatch.setattr(tfm, "Stack", parent)
    monkeypatch.setattr(ray_tpu, "init", lambda **kw: pytest.fail(
        "the program was started"))
    root, name = tiny_root(tmp_path)
    cell = loader.Cell(name, root=root)
    with pytest.raises(SystemExit, match="cannot run this configuration"):
        cell.driver().run(harness.Context(cell, 1, 0.0, False))


def test_the_counts():
    run = {"end_to_end": {"tokens_per_s": 50000.0}, "config": LFM2,
           "facts": {"seq": 8192}, "chips": 1,
           "peak": loader.peaks("TPU v5 lite")}
    metric = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/layer_metrics/step_mfu.conv_moe.json"))
    per_token = flops_lfm2.lfm2_train_flops_per_token(LFM2, 8192)
    assert mfu_from.read(metric, run) == pytest.approx(
        100 * per_token * 50000 / 197e12)
    # by hand: what a token is multiplied by
    by_kind = flops_lfm2.layer_matmul_params(LFM2)
    assert by_kind["E"] == 2048 * 64 + 4 * 8 / 64 * 3 * 2048 * 1536
    assert by_kind["C"] == 4 * 2048 * 2048 + 3 * 2048
    assert by_kind["*"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert by_kind["D"] == 3 * 2048 * 11776
    assert flops_lfm2.pattern(LFM2) == "CD" + "*ECECECE" * 2
    # a step of 4 x 8192 tokens: 59 TFLOP
    assert per_token * 4 * 8192 == pytest.approx(58.97e12, rel=1e-3)
    # the gated convolution: 2K + 1 operations a channel and position,
    # proj in and y out forwards; proj, dy in and d proj out backwards
    ops, nbytes = flops_lfm2.short_conv_cost("fwd", 4, 8192, 2048, 3)
    assert (ops, nbytes) == (4 * 8192 * 2048 * 7, 4 * 8192 * 2048 * 8)
    ops_b, bytes_b = flops_lfm2.short_conv_cost("bwd", 4, 8192, 2048, 3)
    assert ops_b == 2 * ops
    assert bytes_b == 4 * 8192 * 2048 * 14 + 4 * 8 * 3 * 2048 * 4
    with pytest.raises(ValueError):
        flops_lfm2.short_conv_cost("both", 4, 8192, 2048, 3)


def test_the_new_cell_loads_with_its_metrics():
    cell = loader.Cell(CELL)
    assert (cell.chips, cell.workload["driver"]) == (1, "lfm2_train_steps")
    assert (cell.workload["seq"], cell.workload["batch"]) == (8192, 4)
    names = {m["name"] for m in cell.per_layer}
    new = {"step_mfu.conv_moe", "short_conv_block_time_share",
           "short_conv_time_share", "short_conv_fwd_roofline",
           "short_conv_bwd_roofline"}
    assert new | {
        "moe_experts_time_share", "moe_load_max_over_mean",
        "moe_row_movement_time_share", "moe_router_time_share",
        "moe_glu_gmm_roofline", "flash_fwd_roofline", "flash_time_share",
        "flash_bwd_dq_roofline", "flash_bwd_dkdv_roofline",
        "flash_scope_time_share", "step_scope_coverage",
        "device_idle_share.train", "window_compiles", "input_wait_share",
        "step_dispatch_ms", "attention_block_time_share",
        "mlp_block_time_share", "loss_time_share", "step_memory_share",
        "setup_trace_s", "setup_kernel_trace_s"} <= names
    assert not names & {"step_mfu", "step_mfu.hybrid", "step_mfu.moe_swa",
                        "step_mfu.mla_mtp", "step_mfu.latent_moe",
                        "step_mfu.kda_moe", "mamba_block_time_share",
                        "mamba_conv_time_share", "kda_conv_time_share",
                        "mtp_time_share", "collective_exposed_share"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    # nothing of this cell's is read elsewhere
    for other in ("mistral7b_l4_train_s4096", "glm47flash_l7_train_s8192",
                  "nemotron_twotower_l9_train_s8192"):
        assert not new & {m["name"] for m in loader.Cell(other).per_layer}


def test_the_configuration_file_states_its_cut():
    """Every published number under its own key but the four that are
    cut; ``reduced`` names exactly the keys that differ from the
    published values the file states; the count is the program's."""
    config = loader.Cell(CELL).config
    assert config["published"]["num_hidden_layers"] == 40
    assert (config["published"]["num_experts"],
            config["published"]["vocab_size"]) == (64, 65536)
    differ = {k for k, v in config["published"].items() if config[k] != v}
    assert differ == set(config["reduced"]) == set(config["published"])
    entry = loader.named(loader.benchmark_json()["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == differ
    first = config["first_layer"]
    assert config["layer_types"] == config["published"]["layer_types"][
        first:first + config["num_hidden_layers"]]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"],
            config["router_width"], config["experts_held_first"]) == (
        9, 8, 8192, 64, 0)
    widths = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=11776, moe_intermediate_size=1536, norm_eps=1e-05,
        norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
        num_experts_per_tok=4, num_key_value_heads=8,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=1, use_expert_bias=True,
        max_position_embeddings=128000, model_type="lfm2_moe")
    assert {k: config[k] for k in widths} == widths
    published = config["published"]["layer_types"]
    assert [i for i, t in enumerate(published) if t == "full_attention"] \
        == list(range(2, 40, 4))
    assert flops_lfm2.lfm2_params(config) == 832_652_032
    assert "832.65 M parameters" in config["deployment"]
    assert "8 chips share each layer" in config["deployment"]
    assert {"assumed", "departures", "deployment", "mesh", "run"} <= set(
        config)
    assert {"run", "row_buffer", "weights", "tie_word_embeddings",
            "intermediate_size", "router_bias_rate"} <= set(
        config["assumed"])
    assert config["run"]["row_buffer_over_expected"] == 3
    assert config["run"]["router_bias_rate"] == 0.02
