"""SAC + offline RL (BC, CQL) learning gates (reference test model:
rllib tuned_examples regression gates for sac/pendulum and
bc/cql cartpole offline suites)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import (BCConfig, CQLConfig, OfflineData, SACConfig,
                           SACLearner)
from ray_tpu.rllib.env import CartPoleVecEnv, PendulumVecEnv


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


# ------------------------------------------------------------------- env

def test_pendulum_env_contract():
    env = PendulumVecEnv(num_envs=4, seed=0)
    obs = env.reset()
    assert obs.shape == (4, 3)
    # cos^2 + sin^2 = 1 for every row
    np.testing.assert_allclose(obs[:, 0] ** 2 + obs[:, 1] ** 2, 1.0,
                               atol=1e-5)
    for t in range(205):
        obs, r, done, info = env.step(
            np.zeros((4, 1), np.float32))
        assert r.shape == (4,) and (r <= 0).all()
    # 200-step truncation must have fired exactly once per env by now.
    assert info["truncated"].dtype == np.bool_


# --------------------------------------------------------------- learner

def test_sac_learner_updates_all_parts():
    rng = np.random.default_rng(0)
    learner = SACLearner(3, 1, seed=0, act_scale=2.0)
    batch = {
        "obs": rng.normal(size=(64, 3)).astype(np.float32),
        "actions": rng.uniform(-2, 2, (64, 1)).astype(np.float32),
        "rewards": rng.normal(size=64).astype(np.float32),
        "next_obs": rng.normal(size=(64, 3)).astype(np.float32),
        "dones": np.zeros(64, np.float32),
    }
    import jax

    before = jax.tree_util.tree_leaves(learner.state.actor)[0].copy()
    stats = learner.update_from_batch(batch)
    after = jax.tree_util.tree_leaves(learner.state.actor)[0]
    assert not np.allclose(before, after), "actor params did not move"
    for k in ("critic_loss", "actor_loss", "alpha", "entropy"):
        assert np.isfinite(stats[k]), stats


@pytest.mark.slow  # tier-1 budget relief (PR 12): 39.0s measured on a quiet box;
# learning gate — SAC loss/step math stays covered by faster tests
def test_sac_pendulum_learning_gate():
    """Learning-regression gate (VERDICT r4 item 7): swing-up return
    improves from random (~ -1200) to better than -700 within budget."""
    algo = (SACConfig()
            .environment("Pendulum")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=8,
                         rollout_fragment_length=16)
            .training(actor_lr=3e-4, critic_lr=3e-4,
                      train_batch_size=128,
                      num_steps_sampled_before_learning_starts=500,
                      updates_per_iteration=48)
            .build())
    best = -1e9
    try:
        for _ in range(120):
            result = algo.train()
            ret = result["env_runners"]["episode_return_mean"]
            if ret is not None:
                best = max(best, ret)
            if best >= -700.0:
                break
        assert best >= -700.0, f"SAC failed to learn: best return {best}"
    finally:
        algo.stop()


# ----------------------------------------------------------- offline data

def _expert_cartpole_batches(n_steps: int = 1500, noise: float = 0.2,
                             seed: int = 0):
    """Scripted PD-controller expert with epsilon-noise: good actions
    with enough coverage for offline TD."""
    env = CartPoleVecEnv(num_envs=8, seed=seed)
    rng = np.random.default_rng(seed)
    obs = env.reset(seed=seed)
    batches = []
    for _ in range(n_steps):
        expert = (obs[:, 2] + 0.4 * obs[:, 3] > 0).astype(np.int32)
        rand = rng.integers(0, 2, len(expert)).astype(np.int32)
        a = np.where(rng.random(len(expert)) < noise, rand, expert)
        prev = obs
        obs, r, done, info = env.step(a)
        final_obs = info.get("final_obs", obs)
        next_obs = np.where(done[:, None], final_obs, obs)
        batches.append({
            "obs": prev, "actions": a, "rewards": r,
            "next_obs": next_obs,
            "dones": info["terminated"].astype(np.float32),
        })
    return batches


def test_offline_data_roundtrip(cluster):
    batches = _expert_cartpole_batches(n_steps=50)
    data = OfflineData.from_batches(batches)
    assert len(data) == 50 * 8
    rng = np.random.default_rng(0)
    s = data.sample(32, rng)
    assert s["obs"].shape == (32, 4)
    assert s["actions"].dtype in (np.int32, np.int64)
    # Epoch iteration covers the dataset.
    seen = sum(len(b["actions"])
               for b in data.iter_epochs(64, epochs=1))
    assert seen == (len(data) // 64) * 64


def test_offline_data_from_buffer_bridge(cluster):
    from ray_tpu.rllib import ReplayBuffer

    buf = ReplayBuffer(1000, obs_size=4)
    for b in _expert_cartpole_batches(n_steps=20):
        buf.add_batch(b["obs"], b["actions"], b["rewards"],
                      b["next_obs"], b["dones"])
    data = OfflineData.from_buffer(buf)
    assert len(data) == len(buf)


def test_bc_cartpole_learning_gate(cluster):
    """BC clones a noisy expert: greedy eval return far above random
    (~20) — the offline-BC regression gate."""
    data = OfflineData.from_batches(_expert_cartpole_batches())
    algo = (BCConfig()
            .environment("CartPole")
            .training(lr=3e-3, train_batch_size=256,
                      updates_per_iteration=150)
            .offline_data(data)
            .build())
    try:
        ret = -1e9
        for _ in range(6):
            result = algo.train()
            ret = algo.evaluate()["env_runners"]["episode_return_mean"]
            if ret >= 150.0:
                break
        assert ret >= 150.0, f"BC failed to clone the expert: {ret}"
        acc = result["learners"]["default_policy"]["action_accuracy"]
        assert acc > 0.7, acc
    finally:
        algo.stop()


def test_cql_cartpole_learning_gate(cluster):
    """CQL learns a policy from the same fixed dataset via conservative
    TD — the offline value-learning regression gate."""
    data = OfflineData.from_batches(_expert_cartpole_batches())
    algo = (CQLConfig()
            .environment("CartPole")
            .training(lr=1e-3, cql_alpha=1.0, train_batch_size=256,
                      target_network_update_freq=200,
                      updates_per_iteration=200)
            .offline_data(data)
            .build())
    try:
        ret = -1e9
        for _ in range(8):
            algo.train()
            ret = algo.evaluate()["env_runners"]["episode_return_mean"]
            if ret >= 150.0:
                break
        assert ret >= 150.0, f"CQL failed to learn offline: {ret}"
    finally:
        algo.stop()
