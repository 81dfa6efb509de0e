"""The ``mellum_train_steps`` driver end to end at a tiny size on the
CPU, as ``test_hybrid_train_steps.py`` does for the hybrid one; then the
control and this model's planted faults against the limits, the new
reader, and what a program that cannot describe the stack is told."""

import dataclasses
import os

import pytest

from benchmark import compare, flops_mellum, loader, run as harness
from benchmark.readers import mfu_from, roofline_share_from
from benchmark.tests import helpers

CELL = "mellum2_l8_train_s8192"
MELLUM = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs/mellum2_12b_l8_ep4.json"))
# two periods sliding, sliding, full; a window of 12 in 32 positions;
# 8 experts, top-2, experts 2-4 held
TINY_MODEL = dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, sliding_window=12, moe_intermediate_size=24,
    router_width=8, num_experts=3, experts_held_first=2,
    num_experts_per_tok=2, vocab_size=256,
    layer_types=["sliding_attention", "sliding_attention",
                 "full_attention"] * 2,
    mlp_layer_types=["sparse"] * 6, num_hidden_layers=6,
    rope_parameters={
        "full_attention": dict(MELLUM["rope_parameters"]["full_attention"],
                               original_max_position_embeddings=16,
                               factor=4, beta_fast=4),
        "sliding_attention": MELLUM["rope_parameters"]["sliding_attention"]})
# a float32 model, as the hybrid rehearsal's and for its reason: at these
# widths bfloat16's rounding reads more than the control does
TINY_LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 5e-3,
               "grad_share_gap": 5e-3, "change_gap": 5e-2}


def tiny_mellum_root(tmp_path, dtype="float32"):
    root = str(tmp_path)
    spec = loader.benchmark_json(loader.ROOT)
    entry = loader.named(spec["workloads"], CELL, "workload")
    spec["workloads"] = [entry]
    spec["configs"] = [dict(loader.named(spec["configs"], entry["config"],
                                         "config"),
                            file="benchmark/configs/tiny.json")]
    helpers.write(os.path.join(root, "BENCHMARK.json"), spec)
    config = dict(MELLUM, **TINY_MODEL, torch_dtype=dtype)
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
    root, cell = tiny_mellum_root(tmp_path)
    result, out = helpers.drive(monkeypatch, capsys, root, cell,
                                seed=3_000_000_019)
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    for name, limit in TINY_LIMITS.items():
        assert 0 <= result["compared"][name]["value"] <= limit
    assert "M parameters" in out.out and "expert rows" in out.out
    assert "'moe_rows_over': 0}" in out.out.split("window:")[-1]
    again, _ = helpers.drive(monkeypatch, capsys, root, cell,
                             seed=3_000_000_019)
    assert again["compared"] == result["compared"]


def test_traced_run_reports_what_needs_no_device(tmp_path, monkeypatch,
                                                 capsys):
    root, cell = tiny_mellum_root(tmp_path, dtype="bfloat16")
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, trace=1)
    # no TPU plane in a CPU trace: the trace's readers report nothing
    assert set(result["metrics"]) == {
        "step_mfu.moe_swa", "moe_load_max_over_mean", "input_wait_share",
        "step_dispatch_ms"}
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0 < result["metrics"]["step_mfu.moe_swa"]["value"] < 100


@pytest.mark.parametrize("what", ["float8_products", "window_ignored",
                                  "plain_rope", "no_routed", "half_batch"])
def test_the_control_and_the_planted_faults_are_not_correct(tmp_path, what):
    """The reference with 8-bit floating operands, with the window
    ignored, with the full layers' plain rotary table, without the routed
    experts, and on half of each batch, each put in the program's place."""
    from benchmark.references import mellum_decoder as reference

    root, name = tiny_mellum_root(tmp_path)
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
    """The parent commit's ``Stack`` has no window, rotary table by kind,
    softmax router or SwiGLU experts: the driver says so and exits before
    ``ray_tpu.init``."""
    import ray_tpu
    from ray_tpu.models import transformer as tfm

    @dataclasses.dataclass(frozen=True)
    class ParentStack:
        pattern: str = ""
        head_dim: int = 0

    monkeypatch.setattr(tfm, "Stack", ParentStack)
    monkeypatch.setattr(ray_tpu, "init", lambda **kw: pytest.fail(
        "the program was started"))
    root, name = tiny_mellum_root(tmp_path)
    cell = loader.Cell(name, root=root)
    with pytest.raises(SystemExit, match="cannot run this configuration"):
        cell.driver().run(harness.Context(cell, 1, 0.0, False))


class FakeTrace:
    """Twelve calls of the windowed forward kernel, 5 ms each."""
    devices = {0: []}

    def matching(self, pattern, line="ops"):
        assert (pattern, line) == ("swa_fwd", "ops")
        return 0.060, 12.0


def test_roofline_share_from_names_its_module(capsys):
    run = {"trace": FakeTrace(), "config": MELLUM,
           "peak": loader.peaks("TPU v5 lite"),
           "facts": {"batch_per_device": 4, "seq": 8192,
                     "heads_per_device": 32, "kv_heads_per_device": 4,
                     "head_dim": 128}}
    metric = dict(loader.read_json(os.path.join(
        loader.ROOT, "benchmark/layer_metrics/swa_fwd_roofline.json")),
        name="swa_fwd_roofline")
    ops, nbytes = flops_mellum.swa_call_cost("swa_fwd", 4, 8192, 32, 4, 128,
                                             1024)
    least, bound = flops_mellum.least_seconds(ops, nbytes, run["peak"])
    assert bound == "compute"
    assert roofline_share_from.read(metric, run) == pytest.approx(
        100 * least * 12 / 0.060)
    assert "12 calls a device, 5.0000 ms each" in capsys.readouterr().out

    class NothingRan(FakeTrace):
        def matching(self, pattern, line="ops"):
            return 0.0, 0.0

    assert roofline_share_from.read(metric, dict(run, trace=NothingRan())) \
        is None


def test_the_whole_steps_share_names_its_module():
    run = {"end_to_end": {"tokens_per_s": 27000.0}, "config": MELLUM,
           "facts": {"seq": 8192}, "chips": 1,
           "peak": loader.peaks("TPU v5 lite")}
    metric = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/layer_metrics/step_mfu.moe_swa.json"))
    per_token = flops_mellum.mellum_train_flops_per_token(MELLUM, 8192)
    assert mfu_from.read(metric, run) == pytest.approx(
        100 * per_token * 27000 / 197e12)


def test_the_new_cell_loads_with_its_metrics():
    cell = loader.Cell(CELL)
    assert cell.chips == 1 and cell.workload["driver"] == "mellum_train_steps"
    assert (cell.workload["batch"], cell.workload["seq"]) == (2, 8192)
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.moe_swa", "swa_time_share", "swa_fwd_roofline",
            "swa_bwd_dq_roofline", "swa_bwd_dkdv_roofline",
            "moe_glu_gmm_roofline", "moe_experts_time_share",
            "moe_load_max_over_mean", "flash_fwd_roofline",
            "flash_time_share", "step_scope_coverage",
            "device_idle_share.train", "window_compiles"} <= names
    assert not names & {"step_mfu", "step_mfu.hybrid", "moe_gmm_roofline",
                        "ssd_fwd_roofline", "mamba_block_time_share",
                        "collective_exposed_share"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    # no accepted cell gained a metric of this one
    for other in ("mistral7b_l4_train_s4096",
                  "nemotron_twotower_l9_train_s8192"):
        assert not any(m["name"].startswith(("swa_", "moe_glu", "step_mfu.moe"))
                       for m in loader.Cell(other).per_layer)
    config = cell.config
    assert config["published"]["num_experts"] == 64
    assert flops_mellum.mellum_params(config) == 1_077_057_792
