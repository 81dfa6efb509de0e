"""The ``glm_train_steps`` driver end to end at a tiny size on the CPU,
as ``test_mellum_train_steps.py`` does for the Mellum one; then the
control and this model's planted faults against the limits, the counts,
the configuration file's cut, and what a program that cannot describe the
stack is told."""

import dataclasses
import os

import pytest

from benchmark import compare, flops_glm, loader, run as harness
from benchmark.readers import mfu_from
from benchmark.tests import helpers

CELL = "glm47flash_l7_train_s8192"
GLM = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs/glm47_flash_l7_ep8.json"))
# layer 0 dense, two expert layers, the module; 4 heads of 24 + 8 with
# values of 32; 8 experts, top-2, experts 2-4 held
TINY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=32, intermediate_size=96, moe_intermediate_size=24,
    router_width=8, n_routed_experts=3, experts_held_first=2,
    num_experts_per_tok=2, vocab_size=256, num_hidden_layers=3)
# a float32 model, as the other rehearsals' and for their reason: at these
# widths bfloat16's rounding reads more than the control does
TINY_LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 5e-3,
               "grad_share_gap": 5e-3, "change_gap": 5e-2}


def tiny_glm_root(tmp_path, dtype="float32"):
    root = str(tmp_path)
    spec = loader.benchmark_json(loader.ROOT)
    entry = loader.named(spec["workloads"], CELL, "workload")
    spec["workloads"] = [entry]
    spec["configs"] = [dict(loader.named(spec["configs"], entry["config"],
                                         "config"),
                            file="benchmark/configs/tiny.json")]
    helpers.write(os.path.join(root, "BENCHMARK.json"), spec)
    config = dict(GLM, **TINY_MODEL, torch_dtype=dtype)
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
    root, cell = tiny_glm_root(tmp_path)
    result, out = helpers.drive(monkeypatch, capsys, root, cell,
                                seed=3_000_000_019)
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    for name, limit in TINY_LIMITS.items():
        assert 0 <= result["compared"][name]["value"] <= limit
    assert "M parameters" in out.out and "expert rows" in out.out
    assert "in parts [{'main': " in out.out and "'mtp': " in out.out
    assert "'moe_rows_over': 0}" in out.out.split("window:")[-1]
    again, _ = helpers.drive(monkeypatch, capsys, root, cell,
                             seed=3_000_000_019)
    assert again["compared"] == result["compared"]


def test_traced_run_reports_what_needs_no_device(tmp_path, monkeypatch,
                                                 capsys):
    root, cell = tiny_glm_root(tmp_path, dtype="bfloat16")
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, trace=1)
    # no TPU plane in a CPU trace: the trace's readers report nothing
    assert set(result["metrics"]) == {
        "step_mfu.mla_mtp", "moe_load_max_over_mean", "input_wait_share",
        "step_dispatch_ms"}
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0 < result["metrics"]["step_mfu.mla_mtp"]["value"] < 100


@pytest.mark.parametrize("what", [
    "float8_products", "mtp_ignored", "rope_over_whole_head",
    "latent_norms_ignored", "no_routed", "half_batch"])
def test_the_control_and_the_planted_faults_are_not_correct(tmp_path, what):
    """The reference with 8-bit floating operands, without the module's
    loss, with whole heads rotated, with the latents not normed, without
    the routed experts, and on half of each batch, each put in the
    program's place."""
    from benchmark.references import glm_decoder as reference

    root, name = tiny_glm_root(tmp_path)
    cell = loader.Cell(name, root=root)
    driver = cell.driver()
    check = cell.workload["check"]
    assert check["control"] == "float8_products"
    assert check["faults"] == list(reference.FAULTS)
    for seed in (1, 2):
        ctx = harness.Context(cell, seed, 0.0, False)
        ref = driver.follow(ctx)
        broken = (driver.follow(ctx, operand=what)
                  if what in reference.OPERANDS
                  else driver.follow(ctx, rows=1) if what == "half_batch"
                  else driver.follow(ctx, fault=what))
        correct, compared = compare.judge(
            compare.training_numbers(broken, ref), TINY_LIMITS)
        assert not correct, compared
    same, _ = compare.judge(compare.training_numbers(ref, ref), TINY_LIMITS)
    assert same


def test_a_program_that_cannot_describe_the_stack_is_told_at_once(
        tmp_path, monkeypatch):
    """The parent commit's ``Stack`` has no latent attention, leading
    layers or module: the driver says so and exits before
    ``ray_tpu.init``."""
    import ray_tpu
    from ray_tpu.models import transformer as tfm

    @dataclasses.dataclass(frozen=True)
    class ParentStack:
        pattern: str = ""
        head_dim: int = 0
        router_score: str = "sigmoid"
        expert_act: str = "relu2"

    monkeypatch.setattr(tfm, "Stack", ParentStack)
    monkeypatch.setattr(ray_tpu, "init", lambda **kw: pytest.fail(
        "the program was started"))
    root, name = tiny_glm_root(tmp_path)
    cell = loader.Cell(name, root=root)
    with pytest.raises(SystemExit, match="cannot run this configuration"):
        cell.driver().run(harness.Context(cell, 1, 0.0, False))


def test_the_whole_steps_share_names_its_module():
    run = {"end_to_end": {"tokens_per_s": 14000.0}, "config": GLM,
           "facts": {"seq": 8192}, "chips": 1,
           "peak": loader.peaks("TPU v5 lite")}
    metric = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/layer_metrics/step_mfu.mla_mtp.json"))
    per_token = flops_glm.glm_train_flops_per_token(GLM, 8192)
    assert mfu_from.read(metric, run) == pytest.approx(
        100 * per_token * 14000 / 197e12)


def test_the_new_cell_loads_with_its_metrics():
    cell = loader.Cell(CELL)
    assert cell.chips == 1 and cell.workload["driver"] == "glm_train_steps"
    assert (cell.workload["batch"], cell.workload["seq"]) == (2, 8192)
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.mla_mtp", "mla_proj_time_share", "mtp_time_share",
            "moe_glu_gmm_roofline.glm", "moe_experts_time_share",
            "moe_load_max_over_mean", "moe_row_movement_time_share",
            "flash_fwd_roofline", "flash_bwd_dq_roofline",
            "flash_bwd_dkdv_roofline", "flash_time_share",
            "step_scope_coverage", "device_idle_share.train",
            "window_compiles", "input_wait_share", "step_dispatch_ms",
            "attention_block_time_share", "mlp_block_time_share",
            "loss_time_share"} <= names
    assert not names & {"step_mfu", "step_mfu.hybrid", "step_mfu.moe_swa",
                        "moe_gmm_roofline", "moe_glu_gmm_roofline",
                        "ssd_fwd_roofline", "swa_fwd_roofline",
                        "collective_exposed_share"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    # no accepted cell gained a metric of this one
    for other in ("mistral7b_l4_train_s4096", "mellum2_l8_train_s8192",
                  "nemotron_twotower_l9_train_s8192"):
        assert not any(m["name"].endswith((".glm", ".mla_mtp"))
                       or m["name"].startswith(("mla_", "mtp_"))
                       for m in loader.Cell(other).per_layer)
    # the experts' roofline asks this configuration's own key
    metric = next(m for m in cell.per_layer
                  if m["name"] == "moe_glu_gmm_roofline.glm")
    assert metric["args"]["experts"] == "config.n_routed_experts"
    assert "num_experts" not in cell.config


def test_the_configuration_file_states_its_cut():
    """Every published key as the catalog has it but the three that are
    cut; ``reduced`` names exactly the keys that differ from the published
    values the file states; the count is the program's."""
    config = loader.Cell(CELL).config
    assert config["published"] == {"num_hidden_layers": 47,
                                   "n_routed_experts": 64,
                                   "vocab_size": 154880}
    differ = {k for k, v in config["published"].items() if config[k] != v}
    assert differ == set(config["reduced"]) == set(config["published"])
    entry = loader.named(loader.benchmark_json()["configs"],
                         "glm47_flash_l7_ep8", "config")
    assert set(entry["reduced"]) == differ
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["router_width"]) == (7, 8, 19360, 64)
    widths = dict(
        hidden_size=2048, intermediate_size=10240, moe_intermediate_size=1536,
        num_attention_heads=20, num_key_value_heads=20, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=1.8, first_k_dense_replace=1,
        num_nextn_predict_layers=1, rope_theta=1000000, rms_norm_eps=1e-05)
    assert {k: config[k] for k in widths} == widths
    assert flops_glm.glm_params(config) == 920_177_088
    assert "920177088 parameters" in config["deployment"]
    assert {"assumed", "departures", "deployment", "mesh", "run"} <= set(
        config)
