"""The ``nemotron3_train_steps`` driver end to end at a tiny size on the
CPU, as ``test_glm_train_steps.py`` does for the GLM one; then the
control and this model's planted faults, each against the limit its
``why`` names, the counts, the configuration file's cut, and what a
program whose ``Stack`` knows no latent is told."""

import dataclasses
import os

import pytest

from benchmark import (
    compare,
    compare_difference,
    flops_nemotron3,
    loader,
    run as harness,
)
from benchmark.readers import mfu_from
from benchmark.tests import helpers

CELL = "nemotron3super_l9_train_s8192"
CONFIG = "nemotron3_super_l9_ep64"
SUPER = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs", CONFIG + ".json"))
# one short period and the module; 16 Mamba heads in one group (16 a
# group, as the cell); 16 experts, top-4, experts 2-4 held, a latent of 16
TINY_MODEL = dict(
    hidden_size=64, hybrid_override_pattern="ME*E", num_hidden_layers=4,
    mamba_num_heads=16, mamba_head_dim=4, n_groups=1, ssm_state_size=16,
    chunk_size=8, router_width=16, n_routed_experts=3, experts_held_first=2,
    num_experts_per_tok=4, moe_intermediate_size=24, moe_latent_size=16,
    moe_shared_expert_intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, vocab_size=256)
# a float32 model, as the other rehearsals' and for their reason: at these
# widths bfloat16's rounding reads more than the control does
TINY_LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 5e-3,
               "grad_share_gap": 5e-3, "change_gap": 5e-2,
               "first_grad_diff": 5e-3}
# the limit that refuses the control and each planted fault on every seed
# read on the chip (the cell's ``check.why``); at this size the same ones do
REFUSED_BY = {
    "float8_products": "first_grad_diff", "no_routed": "first_grad_gap",
    "mtp_ignored": "loss_gap", "state_reset": "first_grad_diff",
    "unscaled_routed": "first_grad_gap", "router_on_latent": "first_grad_gap",
    "half_batch": "first_grad_gap"}


def tiny_root(tmp_path, dtype="float32"):
    root = str(tmp_path)
    spec = loader.benchmark_json(loader.ROOT)
    entry = loader.named(spec["workloads"], CELL, "workload")
    spec["workloads"] = [entry]
    spec["configs"] = [dict(loader.named(spec["configs"], entry["config"],
                                         "config"),
                            file="benchmark/configs/tiny.json")]
    helpers.write(os.path.join(root, "BENCHMARK.json"), spec)
    config = dict(SUPER, **TINY_MODEL, torch_dtype=dtype)
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
    assert "in parts [{'main': " in out.out and "'mtp': " in out.out
    assert "'moe_rows_over': 0}" in out.out.split("window:")[-1]
    # the counts' parameter total is the set-up line's
    tiny = loader.Cell(cell, root=root).config
    counted = flops_nemotron3.nemotron3_params(tiny)
    assert (f"({counted}; flops_nemotron3 counts {counted})") in out.out
    again, _ = helpers.drive(monkeypatch, capsys, root, cell,
                             seed=3_600_000_019)
    assert again["compared"] == result["compared"]


def test_traced_run_reports_what_needs_no_device(tmp_path, monkeypatch,
                                                 capsys):
    root, cell = tiny_root(tmp_path, dtype="bfloat16")
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, trace=1)
    # no TPU plane in a CPU trace: the trace's readers report nothing
    assert set(result["metrics"]) == {
        "step_mfu.latent_moe", "moe_load_max_over_mean", "input_wait_share",
        "step_dispatch_ms"}
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0 < result["metrics"]["step_mfu.latent_moe"]["value"] < 100


@pytest.mark.parametrize("what", list(REFUSED_BY))
def test_the_control_and_the_planted_faults_are_not_correct(tmp_path, what):
    """The reference with 8-bit floating operands, without the routed
    experts, without the module's loss, with the carried state zeroed at
    every chunk, with the routed part weighed by 1 for 5, with the router
    on the latent, and on half of each batch, each put in the program's
    place: refused, and by the limit its ``why`` names."""
    from benchmark.references import nemotron3_decoder as reference

    root, name = tiny_root(tmp_path)
    cell = loader.Cell(name, root=root)
    driver = cell.driver()
    check = cell.workload["check"]
    assert check["control"] == "float8_products"
    assert check["faults"] == list(reference.FAULTS)
    for seed in (1, 2):
        ctx = harness.Context(cell, seed, 0.0, False)
        ref = driver.follow(ctx, keep=True)
        held = ref.pop("first_grad_leaves")
        more = dict(against=lambda name, entry, key: held[name, entry])
        broken = (driver.follow(ctx, operand=what, **more)
                  if what in reference.OPERANDS
                  else driver.follow(ctx, rows=1, **more)
                  if what == "half_batch"
                  else driver.follow(ctx, fault=what, **more))
        correct, compared = compare.judge(
            compare_difference.training_numbers(broken, ref), TINY_LIMITS)
        print(what, seed, compared)
        assert not correct, compared
        by = compared[REFUSED_BY[what]]
        assert by["value"] > by["limit"], compared
    same, _ = compare.judge(compare_difference.training_numbers(
        driver.follow(ctx, **more), ref), TINY_LIMITS)
    assert same


def test_the_committed_limits_have_the_number_that_refuses_the_control():
    """What the rehearsal above shows at a tiny size holds at the cell's
    only if the committed limits have the same numbers, each with its
    ``why``."""
    check = loader.Cell(CELL).workload["check"]
    assert set(REFUSED_BY.values()) <= set(check["limits"]) == set(
        TINY_LIMITS)
    assert REFUSED_BY[check["control"]] == "first_grad_diff"
    assert "first_grad_diff" in check["why"]
    assert set(REFUSED_BY) == {check["control"], "half_batch",
                               *check["faults"]}


def test_a_program_whose_stack_knows_no_latent_is_told_at_once(
        tmp_path, monkeypatch):
    """The parent commit's ``Stack`` has no ``expert_latent``: the driver
    says so and exits before ``ray_tpu.init``."""
    import ray_tpu
    from ray_tpu.models import transformer as tfm

    parent = dataclasses.make_dataclass(
        "ParentStack", [(f.name, f.type, f) for f in dataclasses.fields(
            tfm.Stack) if f.name != "expert_latent"], frozen=True)
    monkeypatch.setattr(tfm, "Stack", parent)
    monkeypatch.setattr(ray_tpu, "init", lambda **kw: pytest.fail(
        "the program was started"))
    root, name = tiny_root(tmp_path)
    cell = loader.Cell(name, root=root)
    with pytest.raises(SystemExit, match="cannot run this configuration"):
        cell.driver().run(harness.Context(cell, 1, 0.0, False))


def test_the_whole_steps_share_names_its_module():
    run = {"end_to_end": {"tokens_per_s": 11000.0}, "config": SUPER,
           "facts": {"seq": 8192}, "chips": 1,
           "peak": loader.peaks("TPU v5 lite")}
    metric = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/layer_metrics/step_mfu.latent_moe.json"))
    per_token = flops_nemotron3.nemotron3_train_flops_per_token(SUPER, 8192)
    assert mfu_from.read(metric, run) == pytest.approx(
        100 * per_token * 11000 / 197e12)
    # by hand: an expert layer's multiplied parameters a token meets
    met = 22 * 8 / 512
    assert flops_nemotron3.expert_layer_matmul_params(SUPER) == (
        4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
        + met * 2 * 1024 * 2688)
    # the routed experts' products at the latent's width: a quarter of
    # what the accepted file's hidden size would reckon
    from benchmark import flops_hybrid
    ops, _ = flops_hybrid.grouped_mlp_cost(5632, 1024, 2688, 8)
    assert ops == 3 * 2 * 2.0 * 5632 * 1024 * 2688
    assert flops_hybrid.grouped_mlp_cost(5632, 4096, 2688, 8)[0] == 4 * ops


def test_the_new_cell_loads_with_its_metrics():
    cell = loader.Cell(CELL)
    assert (cell.chips, cell.workload["driver"]) == (
        1, "nemotron3_train_steps")
    assert cell.workload["seq"] == 8192 and cell.workload["batch"] in (1, 2)
    names = {m["name"] for m in cell.per_layer}
    new = {"step_mfu.latent_moe", "moe_latent_proj_time_share",
           "moe_router_time_share", "moe_gmm_roofline.latent"}
    assert new | {
        "mamba_block_time_share", "ssd_time_share", "ssd_fwd_roofline",
        "ssd_bwd_roofline", "mamba_conv_time_share",
        "moe_experts_time_share", "moe_load_max_over_mean",
        "moe_row_movement_time_share", "mtp_time_share",
        "flash_fwd_roofline", "flash_bwd_dq_roofline",
        "flash_bwd_dkdv_roofline", "flash_time_share",
        "flash_scope_time_share", "step_scope_coverage",
        "device_idle_share.train", "window_compiles", "input_wait_share",
        "step_dispatch_ms", "attention_block_time_share",
        "mlp_block_time_share", "loss_time_share", "step_memory_share",
        "setup_trace_s", "setup_kernel_trace_s"} <= names
    # the accepted counts know no latent and no module
    assert not names & {"step_mfu", "step_mfu.hybrid", "step_mfu.moe_swa",
                        "step_mfu.mla_mtp", "moe_gmm_roofline",
                        "moe_glu_gmm_roofline", "moe_glu_gmm_roofline.glm",
                        "mla_proj_time_share", "swa_fwd_roofline",
                        "collective_exposed_share"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    # the router's scope runs in every expert cell, so its share is read
    # in all four; nothing else of this cell's is read elsewhere
    for other in ("mistral7b_l4_train_s4096", "mellum2_l8_train_s8192",
                  "nemotron_twotower_l9_train_s8192",
                  "glm47flash_l7_train_s8192"):
        assert new & {m["name"] for m in loader.Cell(other).per_layer} == (
            set() if other.startswith("mistral")
            else {"moe_router_time_share"})
    metric = next(m for m in cell.per_layer
                  if m["name"] == "moe_gmm_roofline.latent")
    assert metric["args"]["hidden"] == "config.moe_latent_size"


def test_the_configuration_file_states_its_cut():
    """Every published key as the catalog has it but the four that are
    cut; ``reduced`` names exactly the keys that differ from the published
    values the file states; the count by part is the program's."""
    config = loader.Cell(CELL).config
    assert config["published"] == {
        "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*E"
        "MEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072}
    assert len(config["published"]["hybrid_override_pattern"]) == 88
    differ = {k for k, v in config["published"].items() if config[k] != v}
    assert differ == set(config["reduced"]) == set(config["published"])
    entry = loader.named(loader.benchmark_json()["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == differ
    assert (config["hybrid_override_pattern"], config["num_hidden_layers"],
            config["n_routed_experts"], config["vocab_size"],
            config["router_width"], config["experts_held_first"]) == (
        "MEMEMEM*E", 9, 8, 16384, 512, 0)
    widths = dict(
        hidden_size=4096, mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, conv_kernel=4, chunk_size=128,
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        num_experts_per_tok=22, routed_scaling_factor=5, moe_latent_size=1024,
        moe_intermediate_size=2688, moe_shared_expert_intermediate_size=5376,
        n_shared_experts=1, num_nextn_predict_layers=1,
        mtp_hybrid_override_pattern="*E", layer_norm_epsilon=1e-05)
    assert {k: config[k] for k in widths} == widths
    parts = flops_nemotron3.params_by_part(config)
    assert parts["by_kind"] == {"M": 109_640_064, "*": 35_655_680,
                                "E": 98_570_752}
    assert (parts["stack"], parts["vocabulary"], parts["module"]) == (
        4 * 109_640_064 + 35_655_680 + 4 * 98_570_752, 134_221_824,
        167_793_152)
    assert flops_nemotron3.nemotron3_params(config) == 1_170_513_920
    assert "1170.5 M parameters" in config["deployment"]
    assert "64 chips share each layer" in config["deployment"]
    assert {"assumed", "departures", "deployment", "mesh", "run"} <= set(
        config)
    assert config["run"]["mtp_weight"] == 0.1
