"""The ``hybrid_train_steps`` driver end to end at a tiny size on the
CPU, as ``test_train_steps.py`` does for the dense one; then the control
and this model's planted faults against the limits, the counts of
``flops_hybrid.py`` by hand, and the two new readers."""

import os

import jax
import pytest

from benchmark import compare, flops_hybrid, loader, run as harness
from benchmark.readers import mfu_from, scope_roofline_share
from benchmark.tests import helpers

CELL = "nemotron_twotower_l9_train_s8192"
NEMOTRON = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs/nemotron_twotower_30b_l9_ep8.json"))
# two periods of a short pattern; 8 experts, top-2, experts 2-4 held
TINY_MODEL = dict(
    hidden_size=64, hybrid_override_pattern="ME*E" * 2, num_hidden_layers=8,
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    chunk_size=8, router_width=8, n_routed_experts=3, experts_held_first=2,
    num_experts_per_tok=2, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, vocab_size=256)
# a float32 model: at these widths bfloat16's rounding reads up to 0.14 on
# the first gradient (a vector of 4 heads), more than the control (0.09)
# and the zeroed state (0.07-0.10) do, so the tiny model trains in
# float32; its gaps are the float32 products' order
TINY_LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 5e-3,
               "grad_share_gap": 5e-3, "change_gap": 5e-2}


def tiny_hybrid_root(tmp_path, dtype="float32"):
    root = str(tmp_path)
    spec = loader.benchmark_json(loader.ROOT)
    entry = loader.named(spec["workloads"], CELL, "workload")
    spec["workloads"] = [entry]
    spec["configs"] = [dict(loader.named(spec["configs"], entry["config"],
                                         "config"),
                            file="benchmark/configs/tiny.json")]
    helpers.write(os.path.join(root, "BENCHMARK.json"), spec)
    config = dict(NEMOTRON, **TINY_MODEL, torch_dtype=dtype)
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
    root, cell = tiny_hybrid_root(tmp_path)
    result, out = helpers.drive(monkeypatch, capsys, root, cell,
                                seed=3_000_000_019)
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    for name, limit in TINY_LIMITS.items():
        assert 0 <= result["compared"][name]["value"] <= limit
    assert "M parameters" in out.out and "expert rows" in out.out
    again, _ = helpers.drive(monkeypatch, capsys, root, cell,
                             seed=3_000_000_019)
    assert again["compared"] == result["compared"]


def test_traced_run_reports_what_needs_no_device(tmp_path, monkeypatch,
                                                 capsys):
    root, cell = tiny_hybrid_root(tmp_path, dtype="bfloat16")
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, trace=1)
    # no TPU plane in a CPU trace: the trace's readers report nothing
    assert set(result["metrics"]) == {
        "step_mfu.hybrid", "moe_load_max_over_mean", "input_wait_share",
        "step_dispatch_ms"}
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0 < result["metrics"]["step_mfu.hybrid"]["value"] < 100


def test_rows_beyond_the_buffer_count_as_failed(tmp_path, monkeypatch,
                                                capsys):
    """A buffer of a quarter of the expected rows: every step leaves rows
    uncomputed, says so, and the run is not correct."""
    from ray_tpu.models import transformer

    monkeypatch.setattr(transformer, "ROWS_OVER_EXPECTED", 0.25)
    root, cell = tiny_hybrid_root(tmp_path)
    result, out = helpers.drive(monkeypatch, capsys, root, cell)
    assert result["failed"] == result["attempted"] >= 1
    assert result["correct"] is False
    assert "'moe_rows_over': 0," not in out.out.split("window:")[-1]
    assert "against a buffer of 16 a layer" in out.out


@pytest.mark.parametrize("what", ["float8_products", "int8_products",
                                  "state_reset", "no_routed"])
def test_the_control_and_the_planted_faults_are_not_correct(tmp_path, what):
    """The reference with 8-bit operands (the cell's float8 control, and
    int8, which at this tiny size is refused too), with the carried state
    zeroed at every chunk boundary, and without the routed experts, each
    put in the program's place."""
    from benchmark.references import nemotron_h_decoder as reference

    root, name = tiny_hybrid_root(tmp_path)
    cell = loader.Cell(name, root=root)
    driver = cell.driver()
    assert cell.workload["check"]["control"] == "float8_products"
    assert what in list(reference.OPERANDS) + cell.workload["check"]["faults"]
    for seed in (1, 2):
        ctx = harness.Context(cell, seed, 0.0, False)
        ref = driver.follow(ctx)
        broken = (driver.follow(ctx, operand=what)
                  if what in reference.OPERANDS
                  else driver.follow(ctx, fault=what))
        correct, compared = compare.judge(
            compare.training_numbers(broken, ref), TINY_LIMITS)
        assert not correct, compared
    same, _ = compare.judge(compare.training_numbers(ref, ref), TINY_LIMITS)
    assert same


def test_hybrid_counts_by_hand():
    # a Mamba-2 layer: in 2688 x 10304, out 4096 x 2688, four taps of 6144
    mamba = 2688 * 10304 + 4096 * 2688 + 4 * 6144
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256
    # router, shared expert, 6 x 16 / 128 of a routed expert
    moe = 2688 * 128 + 2 * 2688 * 3712 + 0.75 * 2 * 2688 * 1856
    assert flops_hybrid.layer_matmul_params(NEMOTRON) == {
        "M": mamba, "*": attention, "E": moe}
    multiplied = 4 * mamba + attention + 4 * moe + 2688 * 16384
    assert flops_hybrid.hybrid_matmul_params(NEMOTRON) == multiplied
    assert round(multiplied / 1e6, 1) == 333.5
    assert flops_hybrid.hybrid_params(NEMOTRON) == 986_254_848
    scan = flops_hybrid.ssd_forward_ops_per_token(64, 64, 8, 128, 128)
    assert scan == 132_096 + 528_384 + 2 * 1_048_576
    causal = 6 * 2 * 4096 * (8192 + 1) / 2
    assert flops_hybrid.hybrid_train_flops_per_token(NEMOTRON, 8192) == \
        6 * multiplied + causal + 4 * 3 * scan
    ops, nbytes = flops_hybrid.ssd_cost("fwd", 4, 8192, 64, 64, 8, 128, 128)
    assert ops == 4 * 8192 * scan
    x = 4 * 8192 * 4096 * 2
    assert nbytes == 2 * x + 2 * 4 * 8192 * 1024 * 2 + 4 * 8192 * 64 * 4
    back = flops_hybrid.ssd_cost("bwd", 4, 8192, 64, 64, 8, 128, 128)
    assert back[0] == 2 * ops and back[1] > nbytes
    least, bound = flops_hybrid.least_seconds(
        ops, nbytes, loader.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.0005 < least < 0.002
    # the routed experts of a layer over 12288 rows: 12 per row, width
    # and hidden; three passes over both banks of 16 experts
    ops, nbytes = flops_hybrid.grouped_mlp_cost(12288, 2688, 1856, 16)
    assert ops == 12 * 12288 * 2688 * 1856
    assert nbytes == 3 * 2 * 16 * 2688 * 1856 * 2 \
        + 6 * 12288 * (2688 + 1856) * 2


class FakeTrace:
    """Three steps of ``train_step`` whose scan took 2 ms forward, 2 ms
    recomputed and 5 ms backward a step on one device."""
    devices = {0: []}

    def matching(self, pattern, line="ops"):
        assert line == "modules"
        return 0.3, 3.0


def test_scope_roofline_share_reads_by_scope_and_pass(monkeypatch):
    table = {"fusion.1": "jit(train_step)/jvp(layers)/while/body/mamba/ssd/x",
             "fusion.2": "jit(train_step)/transpose(jvp(layers))/while/body/"
                         "checkpoint/rematted_computation/mamba/ssd/x",
             "fusion.3": "jit(train_step)/transpose(jvp(layers))/while/body/"
                         "mamba/ssd/x",
             "fusion.4": "jit(train_step)/jvp(layers)/while/body/mamba/"
                         "conv/x"}
    own = {"fusion.1": 0.006, "fusion.2": 0.006, "fusion.3": 0.015,
           "fusion.4": 1.0}
    monkeypatch.setattr(scope_roofline_share, "scope_table_of",
                        lambda run, program: table)
    monkeypatch.setattr(scope_roofline_share, "own_by_op", lambda run: own)
    run = {"trace": FakeTrace(), "config": NEMOTRON,
           "peak": loader.peaks("TPU v5 lite"),
           "facts": {"batch_per_device": 4, "seq": 8192,
                     "ssd_fwd_calls_per_step": 2,
                     "ssd_bwd_calls_per_step": 1}}

    def metric(name):
        return dict(loader.read_json(os.path.join(
            loader.ROOT, "benchmark/layer_metrics", name + ".json")),
            name=name)

    fwd = scope_roofline_share.read(metric("ssd_fwd_roofline"), run)
    least, _ = flops_hybrid.least_seconds(
        *flops_hybrid.ssd_cost("fwd", 4, 8192, 64, 64, 8, 128, 128),
        run["peak"])
    assert fwd == pytest.approx(100 * least * 6 / 0.012)
    bwd = scope_roofline_share.read(metric("ssd_bwd_roofline"), run)
    assert 0 < bwd < 100 and bwd != fwd
    # a program without the record, or a trace without a device: nothing
    monkeypatch.setattr(scope_roofline_share, "scope_table_of",
                        lambda run, program: None)
    assert scope_roofline_share.read(metric("ssd_fwd_roofline"), run) is None


def test_mfu_from_names_its_module():
    run = {"end_to_end": {"tokens_per_s": 40000.0}, "config": NEMOTRON,
           "facts": {"seq": 8192}, "chips": 1,
           "peak": loader.peaks("TPU v5 lite")}
    metric = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/layer_metrics/step_mfu.hybrid.json"))
    per_token = flops_hybrid.hybrid_train_flops_per_token(NEMOTRON, 8192)
    assert mfu_from.read(metric, run) == pytest.approx(
        100 * per_token * 40000 / 197e12)
    assert mfu_from.read(metric, dict(run, end_to_end={})) is None


def test_the_new_cell_loads_with_its_metrics():
    cell = loader.Cell(CELL)
    assert cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.hybrid", "ssd_fwd_roofline", "ssd_bwd_roofline",
            "mamba_block_time_share", "ssd_time_share",
            "moe_experts_time_share", "moe_load_max_over_mean",
            "moe_gmm_roofline", "flash_fwd_roofline",
            "step_scope_coverage"} <= names
    assert "step_mfu" not in names and "collective_exposed_share" not in names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    assert len(jax.devices()) >= 1
