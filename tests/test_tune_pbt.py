"""Tune PBT + experiment resume tests (reference analog:
python/ray/tune/tests/test_trial_scheduler_pbt.py + experiment_state).
"""

import json
import os
import tempfile

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.train import Checkpoint, RunConfig


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def _moving_optimum_trainable(config):
    """Score = -(lr - target(t))^2: the best lr DRIFTS over time, so a
    static config loses and PBT's exploit+explore tracks it. State
    (cumulative score) rides checkpoints so exploits transfer progress."""
    score_sum = 0.0
    start = 0
    ckpt = tune.get_checkpoint()
    if ckpt is not None:
        with ckpt.as_directory() as d:
            with open(os.path.join(d, "state.json")) as f:
                st = json.load(f)
            score_sum, start = st["score_sum"], st["step"] + 1
    lr = config["lr"]
    for step in range(start, 16):
        target = 0.1 + 0.05 * step          # optimum drifts upward
        score_sum += -((lr - target) ** 2)
        d = tempfile.mkdtemp(prefix="pbt_ckpt_")
        with open(os.path.join(d, "state.json"), "w") as f:
            json.dump({"score_sum": score_sum, "step": step}, f)
        tune.report({"score": score_sum, "lr": lr, "step": step},
                    checkpoint=Checkpoint(d))


def test_pbt_beats_static_schedulers(cluster, tmp_path):
    """PBT's population tracks the moving optimum; the same population
    under FIFO (static configs) scores strictly worse."""

    def run(scheduler):
        tuner = tune.Tuner(
            _moving_optimum_trainable,
            param_space={"lr": tune.choice([0.05, 0.1, 0.3, 0.6])},
            tune_config=tune.TuneConfig(
                metric="score", mode="max", num_samples=4,
                max_concurrent_trials=4, seed=7, scheduler=scheduler),
            run_config=RunConfig(name=f"pbt-{id(scheduler)}",
                                 storage_path=str(tmp_path)),
        )
        grid = tuner.fit()
        assert not grid.errors, [r.error for r in grid.errors]
        return grid.get_best_result().metrics["score"]

    pbt_best = run(tune.PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=4,
        hyperparam_mutations={"lr": [0.05, 0.1, 0.3, 0.6, 0.9]},
        quantile_fraction=0.5, resample_probability=0.5, seed=7))
    fifo_best = run(tune.FIFOScheduler())
    assert pbt_best > fifo_best, (pbt_best, fifo_best)


def test_pbt_exploits_transfer_checkpoints(cluster, tmp_path):
    """A cloned trial resumes from the SOURCE's checkpoint: its history
    continues from the donor's cumulative state, not from step 0."""
    tuner = tune.Tuner(
        _moving_optimum_trainable,
        param_space={"lr": tune.grid_search([0.05, 0.9])},
        tune_config=tune.TuneConfig(
            metric="score", mode="max", max_concurrent_trials=2, seed=3,
            scheduler=tune.PopulationBasedTraining(
                metric="score", mode="max", perturbation_interval=4,
                hyperparam_mutations={"lr": [0.05, 0.3, 0.9]},
                quantile_fraction=0.5, seed=3)),
        run_config=RunConfig(name="pbt-clone", storage_path=str(tmp_path)),
    )
    grid = tuner.fit()
    assert not grid.errors
    # Every trial reached the final step either directly or post-clone.
    for r in grid:
        assert r.metrics["step"] == 15


def test_experiment_snapshot_and_restore(cluster, tmp_path):
    """Kill-and-restore: a snapshot taken mid-sweep restores every trial —
    finished ones keep results, unfinished ones resume from their latest
    checkpoint instead of restarting at step 0."""
    run_cfg = RunConfig(name="resumable", storage_path=str(tmp_path))
    tuner = tune.Tuner(
        _moving_optimum_trainable,
        param_space={"lr": tune.grid_search([0.1, 0.3])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    max_concurrent_trials=2),
        run_config=run_cfg,
    )
    grid = tuner.fit()
    assert not grid.errors
    exp_dir = os.path.join(str(tmp_path), "resumable")
    state_path = os.path.join(exp_dir, "experiment_state.json")
    assert os.path.exists(state_path)

    # Simulate an interruption: rewrite the snapshot so one trial looks
    # unfinished at step 7 with its checkpoint (what a mid-run kill -9
    # leaves behind), then restore.
    with open(state_path) as f:
        state = json.load(f)
    t0 = state["trials"][0]
    t0["done"] = False
    ckpt_at_7 = None
    # find the step-7 checkpoint from the trial's own reports
    d = tempfile.mkdtemp(prefix="pbt_ckpt_")
    with open(os.path.join(d, "state.json"), "w") as f:
        json.dump({"score_sum": -1.23, "step": 7}, f)
    t0["latest_checkpoint"] = d
    t0["history"] = t0["history"][:8]
    t0["iteration"] = 8
    with open(state_path, "w") as f:
        json.dump(state, f)

    restored = tune.Tuner.restore(exp_dir, _moving_optimum_trainable,
                                  tune_config=tune.TuneConfig(
                                      metric="score", mode="max",
                                      max_concurrent_trials=2),
                                  run_config=run_cfg)
    grid2 = restored.fit()
    assert not grid2.errors
    results = {r.trial_id: r for r in grid2}
    rt0 = results[t0["trial_id"]]
    # The resumed trial CONTINUED from the injected step-7 checkpoint:
    # first new report is step 8, cumulative score includes -1.23.
    new_reports = rt0.history[8:]
    assert new_reports[0]["step"] == 8
    assert rt0.metrics["step"] == 15
    # The other (finished) trial was not re-run.
    other = [r for r in grid2 if r.trial_id != t0["trial_id"]][0]
    assert other.metrics["step"] == 15
