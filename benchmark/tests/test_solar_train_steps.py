"""The ``solar_train_steps`` driver end to end at a tiny size on the CPU,
as ``test_nemotron3_train_steps.py`` does for the Nemotron-3 one; then
the control and this model's planted faults, each against the limit its
``why`` names, the counts, the configuration file's cut, and what a
program whose ``Stack`` knows no delta-rule kind is told."""

import dataclasses
import os

import pytest

from benchmark import (
    compare,
    compare_difference,
    flops_solar,
    loader,
    run as harness,
)
from benchmark.readers import mfu_from
from benchmark.tests import helpers

CELL = "solaropen2_l4_train_1row"
CONFIG = "solar_open2_l4_ep40"
SOLAR = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs", CONFIG + ".json"))
# the cell's period; 4 delta-rule heads of 16 at chunks of 8; 16 experts,
# top-4, experts 2-4 held
TINY_MODEL = dict(
    hidden_size=64,
    linear_attn_config=dict(SOLAR["linear_attn_config"], num_heads=4,
                            head_dim=16),
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    router_width=16, n_routed_experts=3, experts_held_first=2,
    num_experts_per_tok=4, moe_intermediate_size=24, vocab_size=256)
# a float32 model, as the other rehearsals' and for their reason: at these
# widths bfloat16's rounding reads more than the control does
TINY_LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 5e-3,
               "grad_share_gap": 5e-3, "change_gap": 5e-2,
               "first_grad_diff": 5e-3}
# the limit that refuses the control and each planted fault at this size
REFUSED_BY = {
    "float8_products": "first_grad_diff", "no_routed": "first_grad_gap",
    "scalar_decay": "first_grad_diff", "beta_undoubled": "first_grad_gap",
    "no_decay": "first_grad_gap", "ungated_attention": "first_grad_gap",
    "state_reset": "first_grad_diff"}


def tiny_root(tmp_path, dtype="float32"):
    root = str(tmp_path)
    spec = loader.benchmark_json(loader.ROOT)
    entry = loader.named(spec["workloads"], CELL, "workload")
    spec["workloads"] = [entry]
    spec["configs"] = [dict(loader.named(spec["configs"], entry["config"],
                                         "config"),
                            file="benchmark/configs/tiny.json")]
    helpers.write(os.path.join(root, "BENCHMARK.json"), spec)
    config = dict(SOLAR, **TINY_MODEL, torch_dtype=dtype)
    config["run"] = dict(config["run"], logits_chunk=16, kda_chunk=8)
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
    counted = flops_solar.solar_params(tiny)
    assert (f"({counted}; flops_solar counts {counted})") in out.out


def test_traced_run_reports_what_needs_no_device(tmp_path, monkeypatch,
                                                 capsys):
    root, cell = tiny_root(tmp_path, dtype="bfloat16")
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, trace=1)
    # no TPU plane in a CPU trace: the trace's readers report nothing
    assert {"step_mfu.kda_moe", "moe_load_max_over_mean", "input_wait_share",
            "step_dispatch_ms"} <= set(result["metrics"])
    assert not {"kda_fwd_roofline", "kda_block_time_share"} & set(
        result["metrics"])
    assert 0 < result["metrics"]["step_mfu.kda_moe"]["value"] < 100


@pytest.mark.parametrize("what", list(REFUSED_BY))
def test_the_control_and_the_planted_faults_are_not_correct(tmp_path, what):
    """The reference with 8-bit floating operands, without the routed
    experts, with the decay averaged over a head's channels, with beta in
    (0, 1), without decay, without attention's gate and with the state
    zeroed at every chunk, each put in the program's place: refused, and
    by the limit named above."""
    from benchmark.references import solar_open2_decoder as reference

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
    assert set(REFUSED_BY.values()) <= set(check["limits"]) == set(
        TINY_LIMITS)
    assert REFUSED_BY[check["control"]] == "first_grad_diff"
    assert "first_grad_diff" in check["why"]
    assert set(REFUSED_BY) == {check["control"], *check["faults"]}


def test_a_program_whose_stack_knows_no_delta_rule_is_told_at_once(
        tmp_path, monkeypatch):
    """The parent commit's ``Stack`` has no ``kda_heads``: the driver says
    so and exits before ``ray_tpu.init``."""
    import ray_tpu
    from ray_tpu.models import transformer as tfm

    parent = dataclasses.make_dataclass(
        "ParentStack", [(f.name, f.type, f) for f in dataclasses.fields(
            tfm.Stack) if not f.name.startswith("kda_")
            and f.name != "attention_gate"], frozen=True)
    monkeypatch.setattr(tfm, "Stack", parent)
    monkeypatch.setattr(ray_tpu, "init", lambda **kw: pytest.fail(
        "the program was started"))
    root, name = tiny_root(tmp_path)
    cell = loader.Cell(name, root=root)
    with pytest.raises(SystemExit, match="cannot run this configuration"):
        cell.driver().run(harness.Context(cell, 1, 0.0, False))


def test_the_counts():
    run = {"end_to_end": {"tokens_per_s": 11000.0}, "config": SOLAR,
           "facts": {"seq": 8192}, "chips": 1,
           "peak": loader.peaks("TPU v5 lite")}
    metric = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/layer_metrics/step_mfu.kda_moe.json"))
    per_token = flops_solar.solar_train_flops_per_token(SOLAR, 8192)
    assert mfu_from.read(metric, run) == pytest.approx(
        100 * per_token * 11000 / 197e12)
    # by hand: what a token is multiplied by
    met = 8 * 8 / 320
    by_kind = flops_solar.layer_matmul_params(SOLAR)
    assert by_kind["E"] == 4096 * 320 + 3 * 4096 * 1280 + met * 3 * 4096 * 1280
    assert by_kind["K"] == (3 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192)
                            + 4096 * 64 + 8192 * 4096)
    assert by_kind["*"] == 3 * 4096 * 8192 + 2 * 4096 * 1024
    # the delta rule: a chunk of 64 at a head of 128
    c, d = 64, 128
    a_chunk = (4 * c * c * d + 2 * c ** 3 / 3 + 4 * c * c * d
               + 6 * c * d * d + 2 * c * c * d)
    assert flops_solar.kda_forward_ops_per_token(64, d, c) == pytest.approx(
        64 * a_chunk / c)
    ops, nbytes = flops_solar.kda_cost("fwd", 1, 8192, 64, 128, c)
    assert nbytes == 8192 * (4 * 8192 * 2 + 8192 * 4 + 64 * 4)
    assert flops_solar.kda_cost("bwd", 1, 8192, 64, 128, c)[0] == 2 * ops
    with pytest.raises(ValueError):
        flops_solar.kda_cost("both", 1, 8192, 64, 128, c)
    # one chunk feeds both counts: the run's, which the program runs at and
    # the driver hands the rooflines as a fact
    assert SOLAR["run"]["kda_chunk"] == c
    halved = dict(SOLAR, run=dict(SOLAR["run"], kda_chunk=c // 2))
    assert (per_token - flops_solar.solar_train_flops_per_token(halved, 8192)
            == pytest.approx(3 * 3 * (
                flops_solar.kda_forward_ops_per_token(64, d, c)
                - flops_solar.kda_forward_ops_per_token(64, d, c // 2))))


def test_the_new_cell_loads_with_its_metrics():
    cell = loader.Cell(CELL)
    assert (cell.chips, cell.workload["driver"]) == (1, "solar_train_steps")
    assert (cell.workload["seq"], cell.workload["batch"]) == (8192, 1)
    names = {m["name"] for m in cell.per_layer}
    new = {"step_mfu.kda_moe", "kda_block_time_share",
           "kda_delta_time_share", "kda_fwd_roofline", "kda_bwd_roofline",
           "kda_conv_time_share", "moe_glu_gmm_roofline.solar"}
    assert new | {
        "moe_experts_time_share", "moe_load_max_over_mean",
        "moe_row_movement_time_share", "moe_router_time_share",
        "flash_fwd_roofline", "flash_bwd_dq_roofline",
        "flash_bwd_dkdv_roofline", "flash_time_share",
        "flash_scope_time_share", "step_scope_coverage",
        "device_idle_share.train", "window_compiles", "input_wait_share",
        "step_dispatch_ms", "attention_block_time_share",
        "mlp_block_time_share", "loss_time_share", "step_memory_share",
        "setup_trace_s", "setup_kernel_trace_s"} <= names
    assert not names & {"step_mfu", "step_mfu.hybrid", "step_mfu.moe_swa",
                        "step_mfu.mla_mtp", "step_mfu.latent_moe",
                        "mamba_block_time_share", "ssd_fwd_roofline",
                        "mamba_conv_time_share", "moe_glu_gmm_roofline.glm",
                        "mtp_time_share", "collective_exposed_share"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    # nothing of this cell's is read elsewhere
    for other in ("mistral7b_l4_train_s4096", "glm47flash_l7_train_s8192",
                  "nemotron3super_l9_train_s8192"):
        assert not new & {m["name"] for m in loader.Cell(other).per_layer}


def test_the_configuration_file_states_its_cut():
    """Every published key as the catalog has it but the four that are
    cut; ``reduced`` names exactly the keys that differ from the published
    values the file states; the count is the program's."""
    config = loader.Cell(CELL).config
    assert config["published"] == {
        "num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)),
        "n_routed_experts": 320, "vocab_size": 196608}
    differ = {k for k, v in config["published"].items() if config[k] != v}
    assert differ == set(config["reduced"]) == set(config["published"])
    entry = loader.named(loader.benchmark_json()["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == differ
    assert (config["num_hidden_layers"], config["gqa_layers"],
            config["n_routed_experts"], config["vocab_size"],
            config["router_width"], config["experts_held_first"]) == (
        4, [0], 8, 24576, 320, 0)
    widths = dict(
        hidden_size=4096, num_attention_heads=64, num_key_value_heads=8,
        head_dim=128, num_experts_per_tok=8, routed_scaling_factor=1,
        moe_intermediate_size=1280, intermediate_size=10240,
        n_shared_experts=1, rms_norm_eps=1e-05, use_rope=False,
        use_gqa_gate=True, kda_use_full_proj=False,
        kda_allow_neg_eigval=True, first_k_dense_replace=0, gqa_interval=3,
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                            "num_heads": 64, "num_kv_heads": None})
    assert {k: config[k] for k in widths} == widths
    assert flops_solar.solar_params(config) == 1_295_087_424
    assert "1295.09 M parameters" in config["deployment"]
    assert "40 chips share each layer" in config["deployment"]
    assert {"assumed", "departures", "deployment", "mesh", "run"} <= set(
        config)
    assert {"router", "gqa_gate", "kda", "chunk", "run"} <= set(
        config["assumed"])
