"""Tune-lite: search spaces, concurrent trials, ASHA pruning (reference
test model: python/ray/tune/tests/test_tune_basics, test_trial_scheduler).
"""

import time

import pytest

import ray_tpu
import ray_tpu.tune as tune


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=8)
    yield rt
    ray_tpu.shutdown()


def test_grid_and_random_variants():
    from ray_tpu.tune.search import generate_variants

    space = {"lr": tune.grid_search([0.1, 0.01]),
             "wd": tune.grid_search([0, 1]),
             "h": tune.choice([32, 64]),
             "fixed": 7}
    vs = generate_variants(space, num_samples=2, seed=0)
    assert len(vs) == 2 * 2 * 2  # grid cross-product x samples
    assert all(v["fixed"] == 7 for v in vs)
    assert {(v["lr"], v["wd"]) for v in vs} == {(0.1, 0), (0.1, 1),
                                               (0.01, 0), (0.01, 1)}


def test_tuner_finds_best(cluster):
    def objective(config):
        # Quadratic bowl: best at x=3.
        score = -(config["x"] - 3) ** 2
        tune.report({"score": score, "x": config["x"]})

    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.grid_search([0, 1, 2, 3, 4, 5])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    max_concurrent_trials=3),
    )
    grid = tuner.fit()
    assert len(grid) == 6
    best = grid.get_best_result()
    assert best.config["x"] == 3
    assert best.metrics["score"] == 0


def test_return_style_trainable(cluster):
    def objective(config):
        return {"loss": config["x"] * 2}

    grid = tune.Tuner(
        objective, param_space={"x": tune.grid_search([1, 2])},
        tune_config=tune.TuneConfig(metric="loss", mode="min"),
    ).fit()
    assert grid.get_best_result().metrics["loss"] == 2


def test_trial_error_is_captured(cluster):
    def objective(config):
        if config["x"] == 1:
            raise RuntimeError("bad trial")
        tune.report({"ok": 1})

    grid = tune.Tuner(
        objective, param_space={"x": tune.grid_search([0, 1])},
        tune_config=tune.TuneConfig(metric="ok", mode="max"),
    ).fit()
    assert len(grid.errors) == 1
    assert "bad trial" in grid.errors[0].error
    assert grid.get_best_result().metrics["ok"] == 1


def test_asha_prunes_bad_trials(cluster):
    def objective(config):
        for step in range(12):
            tune.report({"acc": config["quality"] * (step + 1)})

    sched = tune.ASHAScheduler(metric="acc", mode="max", grace_period=2,
                               reduction_factor=2, max_t=12)
    grid = tune.Tuner(
        objective,
        param_space={"quality": tune.grid_search([0.1, 0.2, 0.9, 1.0])},
        tune_config=tune.TuneConfig(metric="acc", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=4),
    ).fit()
    best = grid.get_best_result()
    assert best.config["quality"] == 1.0
    # Successive halving: the weak half dies at the FIRST rung, the
    # runner-up at a later rung, only the winner runs to max_t.
    iters = {r.config["quality"]: len(r.history) for r in grid}
    assert iters[1.0] == 12
    assert iters[0.1] < iters[1.0] and iters[0.2] < iters[1.0]
    assert iters[0.1] <= iters[0.9] and iters[0.2] <= iters[0.9]
    pruned = [r for r in grid
              if r.stopped_early and len(r.history) < len(best.history)]
    assert len(pruned) >= 2
