"""The ``train_steps`` driver end to end at a tiny size on the CPU: the
harness's look for a chip is replaced by the test, everything else is the
run as the chip sees it. Then the control, and the timed path broken
underneath: ``correct`` has to come out false."""

import jax
import pytest

from benchmark import compare, loader, run as harness
from benchmark.tests import helpers

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_run_end_to_end(tmp_path, monkeypatch, capsys):
    root, cell = helpers.tiny_train_root(tmp_path)
    result, out = helpers.drive(monkeypatch, capsys, root, cell,
                                seed=3_000_000_019)
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    assert result["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    for name, limit in helpers.TINY_LIMITS.items():
        assert result["compared"][name]["limit"] == limit
        assert 0 <= result["compared"][name]["value"] <= limit
    # each number compared is on standard error beside its limit
    assert out.err.count("compared ") == len(helpers.TINY_LIMITS)
    # the same seed gives the same comparison; another seed another
    again, _ = helpers.drive(monkeypatch, capsys, root, cell,
                             seed=3_000_000_019)
    assert again["compared"] == result["compared"]
    other, _ = helpers.drive(monkeypatch, capsys, root, cell, seed=5)
    assert other["compared"] != result["compared"] and other["correct"]


def test_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch,
                                                  capsys):
    root, cell = helpers.tiny_train_root(tmp_path)
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, trace=1)
    # no TPU plane in a CPU trace: the trace's readers find nothing and
    # return nothing; the span and rate readers report
    assert set(result["metrics"]) == {"step_mfu", "input_wait_share",
                                      "step_dispatch_ms"}
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_mesh_runs_through_the_same_driver(tmp_path, monkeypatch, capsys):
    """fsdp 2 x tp 2 on four virtual devices is a configuration file,
    not code (float32: XLA's CPU compiler crashes on a bfloat16 psum)."""
    assert len(jax.devices()) >= 4
    root, cell = helpers.tiny_train_root(
        tmp_path, chips=4, mesh={"fsdp": 2, "tp": 2}, dtype="float32")
    result, out = helpers.drive(monkeypatch, capsys, root, cell)
    assert "'dp': 2" in out.out and "'tp': 2" in out.out and "fsdp=True" in out.out
    assert result["correct"] is True and result["device"]["count"] == 4


def broken_step(monkeypatch, breaker):
    """Plant a fault under the driver: ``breaker(real_step)`` stands in
    for the jitted step that build_train_step returns."""
    from ray_tpu.models import training

    real = training.build_train_step

    def build(*args, **kw):
        step, init_fn = real(*args, **kw)
        return jax.jit(breaker(step)), init_fn

    monkeypatch.setattr(training, "build_train_step", build)


FAULTS = {
    # a step that returns its state unchanged
    "state_unchanged": lambda step: lambda p, o, t: (p, o, step(p, o, t)[2]),
    # half of the batch left out, the mean taken over the rest
    "half_batch": lambda step: lambda p, o, t: step(p, o, t[: t.shape[0] // 2]),
}


@pytest.mark.parametrize("fault, mesh", [
    ("state_unchanged", None), ("half_batch", None),
    # the exchange between chips left out: in a GSPMD step the reduction
    # over the data axis is the compiler's, and without it a chip's update
    # comes from its own rows alone, which is the half batch on a mesh
    ("half_batch", {"fsdp": 2, "tp": 2})])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, capsys, fault,
                                      mesh):
    root, cell = (helpers.tiny_train_root(tmp_path) if mesh is None else
                  helpers.tiny_train_root(tmp_path, chips=4, mesh=mesh,
                                          dtype="float32"))
    broken_step(monkeypatch, FAULTS[fault])
    result, _ = helpers.drive(monkeypatch, capsys, root, cell)
    assert result["correct"] is False
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert over & {"first_grad_gap", "change_gap"}, result["compared"]


def test_the_control_is_not_correct(tmp_path, monkeypatch):
    """The reference with int8 operands, put in the program's place."""
    root, name = helpers.tiny_train_root(tmp_path)
    cell = loader.Cell(name, root=root)
    driver = cell.driver()
    for seed in (1, 2, 3):
        ctx = harness.Context(cell, seed, 0.0, False)
        ref = driver.follow(ctx)
        control = driver.follow(ctx, operand=cell.workload["check"]["control"])
        correct, compared = compare.judge(
            compare.training_numbers(control, ref), helpers.TINY_LIMITS)
        assert not correct, compared
        same, _ = compare.judge(compare.training_numbers(ref, ref),
                                helpers.TINY_LIMITS)
        assert same


def test_leaves_the_reference_does_not_move_are_left_out():
    ref = {"loss": [1.0, 1.0], "first_grad": {"a": [1.0, 1.0], "b": [1e-9]},
           "change": {"a": [1.0, 1.0], "b": [1e-9]}}
    program = {"loss": [1.0, 1.0], "first_grad": {"a": [1.0, 1.0], "b": [1e-9]},
               "change": {"a": [1.0, 1.01], "b": [5e-3]}}
    numbers = compare.training_numbers(program, ref)
    assert numbers["change_leaf"] == "a[1]"
    assert numbers["change_gap"] == pytest.approx(0.01)
    # a leaf the program did not move at all reads 1
    program["change"]["a"] = [1.0, 0.0]
    assert compare.training_numbers(program, ref)["change_gap"] == 1.0
