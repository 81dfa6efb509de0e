"""The ``sched_burst`` driver end to end on the CPU over the full
256-node x 32-class cluster with small bursts, then the control and the
timed path broken underneath: ``correct`` has to come out false."""

import numpy as np
import pytest

from benchmark import loader, run as harness
from benchmark.references import hybrid_placement
from benchmark.tests import helpers


def test_run_end_to_end(tmp_path, monkeypatch, capsys):
    root, cell = helpers.tiny_sched_root(tmp_path)
    result, out = helpers.drive(monkeypatch, capsys, root, cell,
                                seed=2_147_483_999, seconds=0.5)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2000 and result["attempted"] % 2000 == 0
    assert set(result["metrics"]) == {"placements_per_s",
                                      "place_latency_p99_ms", "setup_s"}
    assert all(c == {"value": 0, "limit": 0}
               for c in result["compared"].values())
    assert "device solves against the plain reference" in out.out


def test_traced_run(tmp_path, monkeypatch, capsys):
    root, cell = helpers.tiny_sched_root(tmp_path)
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, seconds=0.5,
                              trace=1)
    # the CPU's trace has no TPU plane: only the harness's own readers
    assert set(result["metrics"]) == {"generator_share", "tick_ms",
                                      "device_solves_per_burst"}
    assert result["metrics"]["device_solves_per_burst"]["value"] >= 1


def test_a_backlog_beyond_capacity_is_accounted_for(tmp_path, monkeypatch,
                                                    capsys):
    root, cell = helpers.tiny_sched_root(tmp_path, burst_tasks=34000)
    result, out = helpers.drive(monkeypatch, capsys, root, cell,
                                seconds=0.1)
    assert result["correct"] is True and result["attempted"] == 34000
    line = next(l for l in out.out.splitlines() if "a burst: running" in l)
    queued = int(line.split("queued ")[1].split(",")[0])
    infeasible = int(line.split("infeasible ")[1].split(";")[0])
    assert queued + infeasible > 0, line


def wrong_counts(monkeypatch):
    """A count altered where it is produced: one task more on a node."""
    from ray_tpu.scheduler.policy import BatchedHybridPolicy

    real = BatchedHybridPolicy.schedule_tick_fused

    def altered(self, *args):
        counts = np.array(real(self, *args))
        counts[0, 3] += 1
        return counts

    monkeypatch.setattr(BatchedHybridPolicy, "schedule_tick_fused", altered)


def half_the_burst(monkeypatch):
    """Half of the burst left out where it is handed over."""
    from ray_tpu.core.raylet import Raylet

    real = Raylet.submit_batch
    monkeypatch.setattr(Raylet, "submit_batch",
                        lambda self, tasks: real(self, tasks[::2]))


@pytest.mark.parametrize("fault, number", [
    (wrong_counts, "solve_cells_differing"),
    (half_the_burst, "tasks_unaccounted")])
def test_a_broken_path_is_not_correct(tmp_path, monkeypatch, capsys, fault,
                                      number):
    root, cell = helpers.tiny_sched_root(tmp_path)
    fault(monkeypatch)
    result, _ = helpers.drive(monkeypatch, capsys, root, cell, seconds=0.2)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0


def test_the_control_is_not_correct(tmp_path, monkeypatch, capsys):
    """The reference with one guarantee broken (a resource left out of
    the capacity, so nodes go over it) does not place as the program."""
    root, name = helpers.tiny_sched_root(tmp_path)
    helpers.drive(monkeypatch, capsys, root, name, seconds=0.2)
    cell = loader.Cell(name)
    driver = cell.driver()
    for seed in (1, 2, 3):
        ctx = harness.Context(cell, seed, 0.0, False)
        burst = driver.one_burst(ctx, cell.config, 30000, 1, ctx.spans)
        assert driver.check(ctx, [burst])[0] is True
        correct, compared = driver.check(ctx, [burst], skip_resource=0)
        assert not correct and compared["solve_cells_differing"]["value"] > 0


def test_reference_fills_in_order_and_respects_capacity():
    total = np.array([[8, 16], [8, 16], [4, 0]])
    avail = np.array([[8, 16], [2, 16], [4, 0]])
    alive = np.array([True, True, True])
    reqs = np.array([[2, 4], [1, 0]])
    got = hybrid_placement.place_classes(reqs, [6, 9], total, avail, alive,
                                         1, 0.5)
    # class 0: node 2 lacks resource 1 in total (infeasible); node 0 is
    # idle, node 1 (local) is 75 % used -> order 0, 1; capacities 4, 1
    assert got[0].tolist() == [4, 1, 0]
    # class 1 sees what class 0 took: node 0 full, node 1 full (0 left),
    # node 2 idle with 4
    assert got[1].tolist() == [0, 0, 4]
    used = (got[:, :, None] * reqs[:, None, :]).sum(0)
    assert (used <= avail).all()
