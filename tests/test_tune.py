"""Tests for ray_tpu.tune (reference: python/ray/tune/tests/
test_trial_scheduler.py, test_api.py scenarios, compacted)."""

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune import (
    AsyncHyperBandScheduler,
    MedianStoppingRule,
    PopulationBasedTraining,
    Trainable,
)
from ray_tpu.tune.variant_generator import count_variants, generate_variants


class TestVariantGenerator:
    def test_grid_cross_product(self):
        spec = {"a": tune.grid_search([1, 2]),
                "b": tune.grid_search(["x", "y"]), "c": 5}
        variants = list(generate_variants(spec))
        assert len(variants) == 4
        assert count_variants(spec) == 4
        configs = [v for _, v in variants]
        assert {(c["a"], c["b"]) for c in configs} == \
            {(1, "x"), (1, "y"), (2, "x"), (2, "y")}
        assert all(c["c"] == 5 for c in configs)

    def test_nested_and_sampled(self):
        spec = {"opt": {"lr": tune.uniform(0.1, 0.2),
                        "m": tune.grid_search([0.9, 0.99])}}
        variants = [v for _, v in generate_variants(spec)]
        assert len(variants) == 2
        for v in variants:
            assert 0.1 <= v["opt"]["lr"] <= 0.2
        assert {v["opt"]["m"] for v in variants} == {0.9, 0.99}

    def test_choice_randint(self):
        spec = {"a": tune.choice([1, 2, 3]), "b": tune.randint(0, 10)}
        _, v = next(generate_variants(spec))
        assert v["a"] in (1, 2, 3) and 0 <= v["b"] < 10


# the order in which four trials (score = rate x iteration, rates 1-4,
# 20 iterations) report, and the iteration each must end at
_ASHA_ORDERS = {
    # every trial a step in turn: the rungs fill with better scores
    # before a bad trial's next report
    "in step": ([1, 2, 3, 4] * 20, {1: 3, 2: 5, 3: 9, 4: 20}),
    "in step, best first": ([4, 3, 2, 1] * 20, {1: 2, 2: 2, 3: 2, 4: 20}),
    "one after the other, best first": (
        [4] * 20 + [3] * 20 + [2] * 20 + [1] * 20,
        {1: 2, 2: 2, 3: 2, 4: 20}),
    # each trial finds only worse scores at every rung: nothing to halt
    # it against (what a loaded machine once made of four trials at once)
    "one after the other, worst first": (
        [1] * 20 + [2] * 20 + [3] * 20 + [4] * 20,
        {1: 20, 2: 20, 3: 20, 4: 20}),
}


@pytest.mark.parametrize("order", list(_ASHA_ORDERS))
def test_asha_halts_by_what_the_rung_already_holds(order):
    """``AsyncHyperBandScheduler.on_trial_result`` under fixed
    interleavings of four trials' reports: the best trial always reaches
    ``max_t``, and a worse one is halted as soon as its rung holds enough
    better scores."""
    reports, want = _ASHA_ORDERS[order]
    sched = AsyncHyperBandScheduler(
        time_attr="training_iteration", metric="score", mode="max",
        max_t=20, grace_period=2, reduction_factor=2)
    at = dict.fromkeys((1, 2, 3, 4), 0)
    halted = set()
    for rate in reports:
        if rate in halted:
            continue
        at[rate] += 1
        decision = sched.on_trial_result(None, None, {
            "training_iteration": at[rate], "score": rate * at[rate]})
        if decision == sched.STOP:
            halted.add(rate)
    assert at == want
    assert at[4] == 20


class MyTrainable(Trainable):
    def setup(self, config):
        self.x = config.get("start", 0)
        self.rate = config.get("rate", 1)

    def step(self):
        self.x += self.rate
        return {"score": self.x}

    def save_checkpoint(self, checkpoint_dir=""):
        return {"x": self.x}

    def load_checkpoint(self, checkpoint):
        self.x = checkpoint["x"]

    def reset_config(self, new_config):
        self.rate = new_config.get("rate", 1)
        return True


class TestTuneRun:
    def test_class_trainable_grid(self, ray_start_regular):
        analysis = tune.run(
            MyTrainable,
            config={"rate": tune.grid_search([1, 2, 3])},
            stop={"training_iteration": 4},
            metric="score", mode="max")
        assert len(analysis.trials) == 3
        assert analysis.best_config["rate"] == 3
        assert analysis.best_result["score"] == 12

    def test_function_trainable(self, ray_start_regular):
        def train_fn(config):
            acc = 0.0
            for i in range(5):
                acc += config["lr"]
                tune.report(mean_accuracy=acc, training_iteration=i + 1)

        analysis = tune.run(
            train_fn,
            config={"lr": tune.grid_search([0.1, 0.5])},
            metric="mean_accuracy", mode="max")
        assert analysis.best_config["lr"] == 0.5
        assert analysis.best_result["mean_accuracy"] == pytest.approx(2.5)

    def test_num_samples(self, ray_start_regular):
        analysis = tune.run(
            MyTrainable, config={"rate": tune.choice([1])},
            num_samples=3, stop={"training_iteration": 1},
            metric="score", mode="max")
        assert len(analysis.trials) == 3

    def test_asha_stops_bad_trials(self, ray_start_regular):
        sched = AsyncHyperBandScheduler(
            time_attr="training_iteration", metric="score", mode="max",
            max_t=20, grace_period=2, reduction_factor=2)
        # ASHA halts a trial only against results ALREADY at its rung, so
        # what it halts depends on who reports first. Four trials at once
        # report in whatever order their actors come up (under load the
        # first, worst one could finish all 20 steps before the second
        # began, and then nothing was ever halted): one at a time, best
        # first, fixes the order. The interleavings themselves are
        # test_asha_halts_by_what_the_rung_already_holds.
        analysis = tune.run(
            MyTrainable,
            config={"rate": tune.grid_search([4, 3, 2, 1])},
            scheduler=sched, stop={"training_iteration": 20},
            max_concurrent_trials=1)
        iters = {t.config["rate"]: t.last_result["training_iteration"]
                 for t in analysis.trials}
        # at least one trial must have been halted before max_t
        assert min(iters.values()) < 20
        # and the best trial survived to the end
        assert iters[4] == 20 == max(iters.values())

    def test_hyperband_brackets_halve(self, ray_start_regular):
        from ray_tpu.tune.schedulers import HyperBandScheduler

        sched = HyperBandScheduler(
            time_attr="training_iteration", metric="score", mode="max",
            max_t=9, reduction_factor=3)
        analysis = tune.run(
            MyTrainable,
            config={"rate": tune.grid_search([1, 2, 3, 4, 5, 6])},
            scheduler=sched, stop={"training_iteration": 9})
        iters = sorted(t.last_result["training_iteration"]
                       for t in analysis.trials)
        # a synchronous round must have stopped bottom trials early...
        assert iters[0] < 9
        # ...while the bracket's survivors ran to max_t
        assert iters[-1] == 9
        # the best-rate trial is among the survivors
        best = max(analysis.trials,
                   key=lambda t: t.last_result.get("score", -1))
        assert best.config["rate"] == 6

    def test_median_stopping(self, ray_start_regular):
        sched = MedianStoppingRule(metric="score", mode="max",
                                   grace_period=2, min_samples_required=2)
        analysis = tune.run(
            MyTrainable,
            config={"rate": tune.grid_search([1, 1, 10])},
            scheduler=sched, stop={"training_iteration": 10})
        by_rate = {t.config["rate"]: t for t in analysis.trials}
        assert by_rate[10].last_result["training_iteration"] == 10

    def test_pbt_perturbs(self, ray_start_regular):
        import time

        class PacedTrainable(MyTrainable):
            # PBT perturbs a trial only while another one is live with a
            # score: with instant steps one trial could finish before the
            # other's actor had started (2 of 10 runs), and none was
            def step(self):
                time.sleep(0.02)
                return super().step()

        sched = PopulationBasedTraining(
            time_attr="training_iteration", metric="score", mode="max",
            perturbation_interval=2,
            hyperparam_mutations={"rate": [1, 2, 4, 8]}, seed=0)
        tune.run(
            PacedTrainable,
            config={"rate": tune.grid_search([1, 8])},
            scheduler=sched, stop={"training_iteration": 8})
        assert sched.num_perturbations >= 1

    def test_trial_failure_retry(self, ray_start_regular):
        class Flaky(Trainable):
            def setup(self, config):
                self.i = 0

            def step(self):
                self.i += 1
                if self.i == 2 and self.config.get("boom", True) and \
                        not getattr(Flaky, "_failed", False):
                    Flaky._failed = True
                    raise RuntimeError("boom")
                return {"score": self.i}

        analysis = tune.run(Flaky, config={},
                            stop={"training_iteration": 3},
                            max_failures=1, metric="score", mode="max")
        [t] = analysis.trials
        assert t.status == "TERMINATED"

    def test_with_parameters(self, ray_start_regular):
        import numpy as np

        data = np.arange(100)

        def train_fn(config, data=None):
            tune.report(total=float(data.sum()) * config["f"])

        analysis = tune.run(
            tune.with_parameters(train_fn, data=data),
            config={"f": tune.grid_search([1.0, 2.0])},
            metric="total", mode="max")
        assert analysis.best_result["total"] == float(data.sum()) * 2

    def test_checkpoint_dir_function_api(self, ray_start_regular):
        import os

        def train_fn(config, checkpoint_dir=None):
            start = 0
            if checkpoint_dir:
                with open(os.path.join(checkpoint_dir, "s")) as f:
                    start = int(f.read())
            for i in range(start, 3):
                with tune.checkpoint_dir(step=i) as d:
                    with open(os.path.join(d, "s"), "w") as f:
                        f.write(str(i))
                tune.report(iter=i, training_iteration=i + 1)

        analysis = tune.run(train_fn, config={}, metric="iter", mode="max")
        assert analysis.best_result["iter"] == 2


def test_experiment_checkpoint_and_resume(tmp_path, ray_init):
    """tune.run persists experiment state and resume=True skips finished
    trials, keeping their results in the analysis (reference:
    tune.run(resume=...) over the trial_runner experiment checkpoint +
    syncer.py)."""
    from ray_tpu import tune

    calls = []

    def train_fn(config):
        from ray_tpu import tune as t
        calls.append(config["x"])
        t.report(score=config["x"] * 2)

    a1 = tune.run(train_fn, config={"x": tune.grid_search([1, 2, 3])},
                  metric="score", mode="max", name="resume-exp",
                  local_dir=str(tmp_path))
    assert sorted(calls) == [1, 2, 3]
    assert a1.best_result["score"] == 6
    import os

    assert os.path.exists(
        str(tmp_path / "resume-exp" / "experiment_state.pkl"))
    calls.clear()
    a2 = tune.run(train_fn, config={"x": tune.grid_search([1, 2, 3])},
                  metric="score", mode="max", name="resume-exp",
                  local_dir=str(tmp_path), resume=True)
    assert calls == []  # every trial finished: nothing re-ran
    assert a2.best_result["score"] == 6
    assert len(a2.trials) == 3


def test_sync_config_mirrors_experiment_dir(tmp_path, ray_init):
    from ray_tpu import tune

    up = tmp_path / "bucket"

    def train_fn(config):
        from ray_tpu import tune as t
        t.report(score=1)

    tune.run(train_fn, config={}, metric="score", mode="max",
             name="sync-exp", local_dir=str(tmp_path / "local"),
             sync_config={"upload_dir": str(up)})
    import os

    assert os.path.exists(str(up / "experiment_state.pkl"))


def test_pb2_explores_within_bounds(ray_init):
    """PB2: the explore step proposes GP-bandit values inside
    hyperparam_bounds (reference schedulers/pb2.py)."""
    from ray_tpu import tune
    from ray_tpu.tune.schedulers import PB2

    sched = PB2(time_attr="training_iteration", metric="score",
                mode="max", perturbation_interval=2,
                hyperparam_bounds={"lr": (0.001, 0.1)}, seed=7)

    def train_fn(config):
        from ray_tpu import tune as t
        for i in range(8):
            t.report(score=config["lr"] * (i + 1),
                     training_iteration=i + 1)

    analysis = tune.run(
        train_fn, config={"lr": tune.uniform(0.001, 0.1)},
        num_samples=4, metric="score", mode="max", scheduler=sched)
    for t in analysis.trials:
        assert 0.001 <= t.config["lr"] <= 0.1
    assert len(analysis.trials) == 4


def test_bohb_scheduler_and_searcher(ray_init):
    """BOHB = HyperBandForBOHB bracket scheduling + the multi-fidelity
    TPE searcher; converges onto the good region of a quadratic."""
    from ray_tpu import tune
    from ray_tpu.tune.schedulers import HyperBandForBOHB
    from ray_tpu.tune.suggest.bohb import BOHBSearcher

    def train_fn(config):
        from ray_tpu import tune as t
        for i in range(9):
            t.report(score=-(config["x"] - 0.7) ** 2,
                     training_iteration=i + 1)

    searcher = BOHBSearcher(metric="score", mode="max",
                            n_initial_points=3, seed=3)
    sched = HyperBandForBOHB(time_attr="training_iteration",
                             metric="score", mode="max", max_t=9,
                             reduction_factor=3)
    analysis = tune.run(
        train_fn, config={"x": tune.uniform(0.0, 1.0)},
        num_samples=12, metric="score", mode="max",
        scheduler=sched, search_alg=searcher)
    assert analysis.best_result["score"] > -0.2
    # the searcher actually built per-budget buckets
    assert searcher._buckets
