"""RLlib-lite tests: vec env contract, GAE correctness, distributed env
runners, and the PPO learning-regression gate (reference analog:
rllib/algorithms/ppo/tests/test_ppo.py learning tests + CartPole gate).
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import (CartPoleVecEnv, EnvRunnerGroup, PPO, PPOConfig,
                           PPOLearner)


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_vec_env_auto_reset_and_truncation():
    env = CartPoleVecEnv(num_envs=4, max_steps=8, seed=0)
    obs = env.reset(seed=0)
    assert obs.shape == (4, 4)
    saw_truncation = False
    rng = np.random.default_rng(0)
    for step in range(60):  # random policy outlives max_steps=8 regularly
        obs, reward, done, info = env.step(rng.integers(0, 2, 4))
        assert obs.shape == (4, 4) and reward.shape == (4,)
        assert info["terminated"].dtype == np.bool_
        assert info["truncated"].dtype == np.bool_
        # terminated and truncated are disjoint by contract.
        assert not (info["terminated"] & info["truncated"]).any()
        assert (done == (info["terminated"] | info["truncated"])).all()
        if info["truncated"].any():
            saw_truncation = True
            # final_obs carries the pre-reset state; after auto-reset the
            # new obs is near the init distribution (|x| <= 0.05).
            idx = np.flatnonzero(info["truncated"])
            assert (np.abs(obs[idx]) <= 0.05 + 1e-6).all()
    assert saw_truncation


def test_gae_truncation_bootstraps_with_critic():
    """Truncated steps must bootstrap from v(final_obs), not 0."""
    import jax.numpy as jnp

    learner = PPOLearner(4, 2, gamma=0.5, gae_lambda=1.0, seed=0)
    T, B = 3, 1
    batch = {
        "values": jnp.array([[1.0], [2.0], [3.0]]),
        "rewards": jnp.array([[1.0], [1.0], [1.0]]),
        "terminated": jnp.zeros((T, B)),
        "truncated": jnp.array([[0.0], [1.0], [0.0]]),
        "bootstrap_value": jnp.array([[0.0], [5.0], [0.0]]),
        "last_value": jnp.array([4.0]),
    }
    adv, targets = learner._gae(batch)
    g, lam = 0.5, 1.0
    # t=1 is truncated: v_next = bootstrap (5.0), episode still bootstraps
    # (not_terminal = 1) but the GAE chain CUTS at the done boundary.
    d2 = 1.0 + g * 4.0 - 3.0            # t=2: v_next = last_value
    d1 = 1.0 + g * 5.0 - 2.0            # t=1: v_next = bootstrap_value
    d0 = 1.0 + g * 1.0 * 2.0 - 1.0      # t=0: v_next = values[1]
    a2 = d2
    a1 = d1                              # chain cut by done at t=1
    a0 = d0 + g * lam * a1
    np.testing.assert_allclose(np.asarray(adv)[:, 0], [a0, a1, a2],
                               rtol=1e-5)
    # Terminated instead: same shape but v_next contribution is zero.
    batch["truncated"] = jnp.zeros((T, B))
    batch["terminated"] = jnp.array([[0.0], [1.0], [0.0]])
    adv_term, _ = learner._gae(batch)
    d1t = 1.0 - 2.0
    np.testing.assert_allclose(np.asarray(adv_term)[1, 0], d1t, rtol=1e-5)


def test_local_env_runner_rollout_shapes():
    group = EnvRunnerGroup("CartPole", num_env_runners=0,
                           num_envs_per_runner=4, rollout_len=16, seed=0)
    learner = PPOLearner(4, 2, seed=0)
    group.sync_weights(learner.get_weights())
    (rollout,) = group.sample()
    assert rollout["obs"].shape == (16, 4, 4)
    assert rollout["actions"].shape == (16, 4)
    for key in ("logp", "values", "rewards", "terminated", "truncated",
                "bootstrap_value"):
        assert rollout[key].shape == (16, 4), key
    assert rollout["last_value"].shape == (4,)
    stats = learner.update_from_batch(rollout)
    assert np.isfinite(stats["total_loss"])


def test_remote_env_runner_group(cluster):
    """The distributed rollout path: remote runner actors + weight sync
    through the object store."""
    group = EnvRunnerGroup("CartPole", num_env_runners=2,
                           num_envs_per_runner=4, rollout_len=8, seed=0)
    try:
        learner = PPOLearner(4, 2, seed=0)
        group.sync_weights(learner.get_weights())
        rollouts = group.sample()
        assert len(rollouts) == 2
        for r in rollouts:
            assert r["obs"].shape == (8, 4, 4)
        metrics = group.get_metrics()
        assert len(metrics) == 2
        # Weights propagate: rollouts from updated weights still sane.
        batch = rollouts[0]
        learner.update_from_batch(batch)
        group.sync_weights(learner.get_weights())
        rollouts2 = group.sample()
        assert rollouts2[0]["actions"].shape == (8, 4)
    finally:
        group.stop()


@pytest.mark.slow  # tier-1 budget relief (PR 12): 50.3s measured on a quiet box;
# learning gate — PPO step mechanics stay covered by faster tests
def test_ppo_cartpole_learning_gate():
    """The learning-regression gate: CartPole mean return >= 450 within a
    bounded iteration budget (reference: PPO CartPole learning tests)."""
    algo = (PPOConfig()
            .environment("CartPole")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=16,
                         rollout_fragment_length=256)
            .training(lr=3e-4, minibatch_size=512)
            .build())
    best = 0.0
    for i in range(80):
        result = algo.train()
        ret = result["env_runners"]["episode_return_mean"]
        if ret is not None:
            best = max(best, ret)
        if best >= 450.0:
            break
    assert best >= 450.0, f"PPO failed to reach 450 on CartPole (best {best})"
