"""Tune layer: variant generation, trial loop, schedulers, PBT, restore,
and the Train-on-Tune integration (reference test model:
``python/ray/tune/tests/test_tune_*.py``)."""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.tune import TuneConfig, Tuner


def test_generate_variants_grid_and_samples():
    from ray_tpu.tune.search_space import generate_variants

    space = {"a": tune.grid_search([1, 2]), "b": tune.uniform(0, 1), "c": 7}
    variants = generate_variants(space, num_samples=3, seed=0)
    assert len(variants) == 6  # 2 grid x 3 samples
    assert {v["a"] for v in variants} == {1, 2}
    assert all(0 <= v["b"] <= 1 for v in variants)
    assert all(v["c"] == 7 for v in variants)


def test_nested_space_and_domains():
    from ray_tpu.tune.search_space import generate_variants

    space = {
        "opt": {"lr": tune.loguniform(1e-4, 1e-1), "wd": tune.choice([0, 0.1])},
        "layers": tune.randint(1, 5),
    }
    (v,) = generate_variants(space, 1, seed=1)
    assert 1e-4 <= v["opt"]["lr"] <= 1e-1
    assert v["opt"]["wd"] in (0, 0.1)
    assert 1 <= v["layers"] < 5


def test_function_trainable_basic(rt_cluster, tmp_path):
    def objective(config):
        for i in range(3):
            tune.report({"score": config["x"] * (i + 1)})

    grid = Tuner(
        objective,
        param_space={"x": tune.grid_search([1, 2, 3])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(name="basic", storage_path=str(tmp_path)),
    ).fit()
    assert len(grid) == 3
    best = grid.get_best_result()
    assert best.config["x"] == 3
    assert best.metrics["score"] == 9
    assert grid.num_terminated == 3


def test_class_trainable_and_stop_criteria(rt_cluster, tmp_path):
    class MyTrainable(tune.Trainable):
        def setup(self, config):
            self.x = config["x"]

        def step(self):
            return {"value": self.x * self._iteration}

    grid = Tuner(
        MyTrainable,
        param_space={"x": tune.grid_search([1, 2])},
        tune_config=TuneConfig(metric="value", mode="max"),
        run_config=RunConfig(name="cls", storage_path=str(tmp_path),
                             stop={"training_iteration": 4}),
    ).fit()
    assert len(grid) == 2
    for r in grid:
        assert r.metrics["training_iteration"] == 4


def test_asha_stops_bad_trials(rt_cluster, tmp_path):
    def objective(config):
        for i in range(20):
            tune.report({"acc": config["q"] * (i + 1)})

    grid = Tuner(
        objective,
        param_space={"q": tune.grid_search([0.1, 0.2, 0.9, 1.0])},
        tune_config=TuneConfig(
            metric="acc", mode="max",
            scheduler=tune.AsyncHyperBandScheduler(
                max_t=20, grace_period=2, reduction_factor=2)),
        run_config=RunConfig(name="asha", storage_path=str(tmp_path)),
    ).fit()
    iters = {r.config["q"]: r.metrics.get("training_iteration", 0) for r in grid}
    # the best trial is never rung-stopped; at least one bad trial is
    assert iters[1.0] == 20
    assert min(iters[0.1], iters[0.2]) < 20


def test_tune_failure_and_retry(rt_cluster, tmp_path):
    marker = os.path.join(str(tmp_path), "failed_once")

    def flaky(config):
        if not os.path.exists(marker):
            with open(marker, "w") as f:
                f.write("x")
            raise RuntimeError("boom")
        tune.report({"ok": 1})

    grid = Tuner(
        flaky,
        param_space={},
        tune_config=TuneConfig(metric="ok", mode="max"),
        run_config=RunConfig(
            name="flaky", storage_path=str(tmp_path),
            failure_config=tune.FailureConfig(max_failures=2)),
    ).fit()
    assert grid.get_best_result().metrics["ok"] == 1


def test_tune_error_reported(rt_cluster, tmp_path):
    def bad(config):
        raise ValueError("always fails")

    grid = Tuner(
        bad, param_space={},
        run_config=RunConfig(name="bad", storage_path=str(tmp_path)),
    ).fit()
    assert len(grid.errors) == 1
    assert "always fails" in grid.errors[0]


def test_pbt_mutates_from_checkpoint(rt_cluster, tmp_path):
    # A step takes no time, so a weak trial whose actor is up first can run
    # all eight before the strong trial's actor has started, and PBT then has
    # nobody to clone from (seen whenever the suite's load delayed the second
    # actor). The weak trial waits for the strong one's first step: a wait on
    # the condition the test is about, not on a clock.
    strong_stepped = str(tmp_path / "strong_stepped")

    class PBTTrainable(tune.Trainable):
        def setup(self, config):
            self.lr = config["lr"]
            self.level = 0

        def step(self):
            if self.lr >= 0.1:
                open(strong_stepped, "w").close()
            else:
                deadline = time.time() + 120
                while not os.path.exists(strong_stepped) \
                        and time.time() < deadline:
                    time.sleep(0.01)
            self.level += self.lr
            return {"level": self.level, "lr": self.lr}

        def save_checkpoint(self, d):
            return {"level": self.level}

        def load_checkpoint(self, data):
            self.level = data["level"]

    grid = Tuner(
        PBTTrainable,
        param_space={"lr": tune.grid_search([0.01, 1.0])},
        tune_config=TuneConfig(
            metric="level", mode="max",
            scheduler=tune.PopulationBasedTraining(
                perturbation_interval=2,
                hyperparam_mutations={"lr": tune.uniform(0.5, 2.0)})),
        run_config=RunConfig(name="pbt", storage_path=str(tmp_path),
                             stop={"training_iteration": 8}),
    ).fit()
    # the weak trial should have been exploited toward the strong one's lr
    levels = sorted(r.metrics["level"] for r in grid)
    assert levels[-1] >= 7.9  # strong trial ran unimpeded
    assert levels[0] > 0.08 * 8  # weak trial improved beyond pure lr=0.01


def test_experiment_state_and_restore(rt_cluster, tmp_path):
    def objective(config):
        tune.report({"v": config["x"]})

    Tuner(
        objective, param_space={"x": tune.grid_search([5, 6])},
        tune_config=TuneConfig(metric="v", mode="max"),
        run_config=RunConfig(name="exp", storage_path=str(tmp_path)),
    ).fit()
    state_path = os.path.join(str(tmp_path), "exp", "experiment_state.json")
    assert os.path.exists(state_path)
    restored = Tuner.restore(os.path.join(str(tmp_path), "exp"), objective,
                             tune_config=TuneConfig(metric="v", mode="max"))
    grid = restored.fit()  # all TERMINATED -> nothing re-runs
    assert grid.num_terminated == 2


def test_trainer_on_tune(rt_cluster, tmp_path):
    def loop(config):
        from ray_tpu import train

        for i in range(2):
            train.report({"loss": config["lr"] * (i + 1)})

    trainer = JaxTrainer(
        loop, train_loop_config={"lr": 1.0},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="inner", storage_path=str(tmp_path)))
    grid = Tuner(
        trainer,
        param_space={"train_loop_config": {"lr": tune.grid_search([0.5, 2.0])}},
        tune_config=TuneConfig(metric="loss", mode="min"),
        run_config=RunConfig(name="trainer_tune", storage_path=str(tmp_path)),
    ).fit()
    assert len(grid) == 2
    assert grid.get_best_result().config["train_loop_config"]["lr"] == 0.5


def test_quasi_random_search(rt_cluster, tmp_path):
    def objective(config):
        tune.report({"obj": -(config["x"] - 3.0) ** 2})

    grid = Tuner(
        objective,
        param_space={"x": tune.uniform(0, 10)},
        tune_config=TuneConfig(
            metric="obj", mode="max",
            search_alg=tune.QuasiRandomSearch(num_samples=10, seed=3),
            max_concurrent_trials=2),
        run_config=RunConfig(name="qrs", storage_path=str(tmp_path)),
    ).fit()
    assert len(grid) == 10
    best = grid.get_best_result()
    assert best.metrics["obj"] > -9.0


def test_tpe_searcher_finds_optimum(rt_cluster):
    """Native TPE beats the search space's average on a smooth objective:
    minimize (x-0.7)^2 + penalty for wrong category."""
    from ray_tpu import tune
    from ray_tpu.tune import TPESearcher

    def objective(config):
        loss = (config["x"] - 0.7) ** 2
        if config["algo"] != "good":
            loss += 0.5
        tune.report({"loss": loss})

    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.uniform(0.0, 1.0),
                     "algo": tune.choice(["good", "bad", "ugly"])},
        tune_config=tune.TuneConfig(
            metric="loss", mode="min", num_samples=40,
            # a live-trial cap so results flow back BEFORE later suggests —
            # without it all 40 configs are drawn pre-observation and the
            # model-guided phase never runs
            max_concurrent_trials=4,
            search_alg=TPESearcher(n_initial=8, seed=0)))
    results = tuner.fit()
    best = results.get_best_result()
    assert best.metrics["loss"] < 0.05, best.metrics
    assert best.config["algo"] == "good"
    # the model-guided phase concentrates sampling near the optimum: its
    # AVERAGE loss beats the random warm-up's average (min-vs-min would be
    # a coin flip — one lucky random draw breaks it)
    losses = [r.metrics["loss"] for r in results]
    assert np.mean(losses[20:]) < np.mean(losses[:8])


def test_trial_loggers_jsonl_csv_tb(rt_cluster, tmp_path):
    """Every trial writes result.json (JSONL), progress.csv, and TB events
    (reference: tune/logger defaults)."""
    import glob
    import json as _json

    def objective(config):
        for i in range(3):
            tune.report({"score": config["x"] * (i + 1), "iter": i})

    Tuner(
        objective,
        param_space={"x": tune.grid_search([1.0, 2.0])},
        tune_config=TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(name="logex", storage_path=str(tmp_path)),
    ).fit()
    trial_dirs = [d for d in glob.glob(str(tmp_path / "logex" / "*"))
                  if os.path.isdir(d)]
    assert len(trial_dirs) == 2
    for d in trial_dirs:
        lines = open(os.path.join(d, "result.json")).read().splitlines()
        rows = [_json.loads(l) for l in lines]
        # 3 reports (+ possibly a final done-marker result)
        assert {r.get("iter") for r in rows} >= {0, 1, 2}
        csv_lines = open(os.path.join(d, "progress.csv")).read().splitlines()
        assert len(csv_lines) >= 4  # header + 3 rows
        assert "score" in csv_lines[0]
        try:
            import torch.utils.tensorboard  # noqa: F401
            has_tb = True
        except Exception:  # noqa: BLE001
            has_tb = False
        if has_tb:  # TB is documented-optional; only assert when available
            assert glob.glob(os.path.join(d, "events.out.tfevents.*"))


def test_resource_changing_scheduler(rt_cluster, tmp_path):
    """ResourceChangingScheduler (reference:
    tune/schedulers/resource_changing_scheduler.py): the allocator's
    proposal checkpoint-pauses the trial and relaunches its runner with the
    new resources — observable as a deeper CPU hold on the cluster."""
    def allocator(trials, trial, result):
        if result.get("training_iteration", 0) >= 2:
            return {"cpu": 2}
        return None

    def objective(config):
        for i in range(6):
            tune.report({"pid": os.getpid(), "score": i})

    tuner = Tuner(
        objective,
        param_space={"x": 1},
        tune_config=TuneConfig(
            num_samples=1,
            scheduler=tune.ResourceChangingScheduler(
                resources_allocation_function=allocator)),
        run_config=RunConfig(name="rcs", storage_path=str(tmp_path)),
    )
    results = tuner.fit()
    (res,) = list(results)
    hist = res.metrics_history
    # the proposal checkpoint-paused the trial and RELAUNCHED its runner
    # (fresh worker process) with the new resources; training continued
    # from the checkpoint to all 6 iterations
    assert len({h["pid"] for h in hist}) == 2, hist
    # the function restarted from its last checkpoint: iteration counting
    # continued across the relaunch
    assert hist[-1]["training_iteration"] >= 6


def test_resource_changing_scheduler_decision_unit():
    """Unit: an allocator proposal pauses the trial and records the new
    per-trial resources; no proposal continues."""
    from ray_tpu.tune.schedulers import CONTINUE, PAUSE
    from ray_tpu.tune.trial import Trial

    calls = []

    def alloc(trials, trial, result):
        calls.append(result["training_iteration"])
        return {"cpu": 3} if result["training_iteration"] >= 2 else None

    s = tune.ResourceChangingScheduler(resources_allocation_function=alloc)
    t = Trial("t1", {"x": 1})
    s.on_trial_add(t)
    assert s.on_trial_result(t, {"training_iteration": 1}) == CONTINUE
    assert t.resources is None
    assert s.on_trial_result(t, {"training_iteration": 2}) == PAUSE
    assert t.resources == {"cpu": 3}
    # same proposal again: no change, no second pause
    assert s.on_trial_result(t, {"training_iteration": 3}) == CONTINUE
    assert calls == [1, 2, 3]
