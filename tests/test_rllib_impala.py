"""IMPALA (V-trace async actor-learner) + multi-agent runner tests
(reference analog: rllib/algorithms/impala/tests/ + multi-agent env runner
tests)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.impala import IMPALA, IMPALAConfig, IMPALALearner
from ray_tpu.rllib.multi_agent import (IndependentEnsembleEnv,
                                       MultiAgentEnvRunner,
                                       MultiAgentPPO, MultiAgentPPOConfig)


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_vtrace_on_policy_reduces_to_discounted_return():
    """With on-policy data (ratio == 1) and no termination inside the
    window, V-trace targets telescope to the discounted bootstrap return:
    vs_t = sum_k gamma^k r_{t+k} + gamma^{T-t} V(x_T)."""
    import jax.numpy as jnp

    learner = IMPALALearner(4, 2, gamma=0.9, seed=0)
    T, B = 5, 2
    values = jnp.asarray(np.linspace(0.0, 1.0, T * B).reshape(T, B),
                         jnp.float32)
    last_value = jnp.asarray([2.0, 3.0], jnp.float32)
    batch = {
        "rewards": jnp.ones((T, B), jnp.float32),
        "terminated": jnp.zeros((T, B), jnp.float32),
        "truncated": jnp.zeros((T, B), jnp.float32),
        "bootstrap_value": jnp.zeros((T, B), jnp.float32),
    }
    rho = jnp.ones((T, B), jnp.float32)
    vs, pg_adv = learner._vtrace(values, last_value, batch, rho)

    g = 0.9
    expected = np.zeros((T, B))
    for t in range(T):
        ret = sum(g ** k for k in range(T - t))  # unit rewards
        expected[t] = ret + g ** (T - t) * np.asarray(last_value)
    np.testing.assert_allclose(np.asarray(vs), expected, rtol=1e-5)
    # pg advantage at t uses vs_{t+1}: rho * (r + gamma*vs_next - V)
    vs_next = np.concatenate([np.asarray(vs)[1:],
                              np.asarray(last_value)[None]], 0)
    np.testing.assert_allclose(
        np.asarray(pg_adv), 1.0 + g * vs_next - np.asarray(values),
        rtol=1e-5)


def test_vtrace_termination_zeroes_continuation():
    """A terminated step must not leak the next state's value into targets."""
    import jax.numpy as jnp

    learner = IMPALALearner(4, 2, gamma=0.9, seed=0)
    T, B = 3, 1
    values = jnp.zeros((T, B), jnp.float32)
    last_value = jnp.asarray([100.0], jnp.float32)
    term = jnp.zeros((T, B), jnp.float32).at[1, 0].set(1.0)
    batch = {
        "rewards": jnp.ones((T, B), jnp.float32),
        "terminated": term,
        "truncated": jnp.zeros((T, B), jnp.float32),
        "bootstrap_value": jnp.zeros((T, B), jnp.float32),
    }
    vs, _ = learner._vtrace(values, last_value, batch,
                            jnp.ones((T, B), jnp.float32))
    # t=1 terminates: vs_1 = r = 1 exactly; t=0 = 1 + 0.9*1.
    np.testing.assert_allclose(np.asarray(vs)[:2, 0], [1.9, 1.0], rtol=1e-5)
    # t=2 (fresh episode) bootstraps the big last_value.
    assert float(vs[2, 0]) > 50.0


def test_impala_local_learning_gate():
    """Learning-regression gate: V-trace actor-critic clears a CartPole
    return bar within a bounded budget (reference: IMPALA CartPole tuned
    example). Single-pass updates learn slower than PPO's 4-epoch loop,
    so the bar is lower and the budget bigger."""
    algo = (IMPALAConfig()
            .environment("CartPole")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=16,
                         rollout_fragment_length=128)
            .training(lr=1e-3, entropy_coeff=0.01)
            .build())
    best = 0.0
    for _ in range(150):
        result = algo.train()
        ret = result["env_runners"]["episode_return_mean"]
        if ret is not None:
            best = max(best, ret)
        if best >= 150.0:
            break
    assert best >= 150.0, f"IMPALA failed to reach 150 (best {best})"


def test_impala_async_runners(cluster):
    """Async pipeline: 2 remote runners stay armed; each training_step
    consumes exactly one rollout and re-arms its runner."""
    algo = (IMPALAConfig()
            .environment("CartPole")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                         rollout_fragment_length=32)
            .build())
    try:
        assert len(algo._inflight) == 2
        for _ in range(4):
            stats = algo.training_step()
            assert np.isfinite(stats["total_loss"])
            assert len(algo._inflight) == 2  # re-armed
        assert algo._total_steps == 4 * 32 * 4
    finally:
        algo.stop()


# ---------------------------------------------------------------- multi-agent


def test_multi_agent_runner_shapes():
    def ctor(num_envs, seed):
        return IndependentEnsembleEnv(
            {"a0": "CartPole", "a1": "CartPole"}, num_envs=num_envs,
            seed=seed)

    runner = MultiAgentEnvRunner(ctor, num_envs=4, rollout_len=8,
                                 policy_mapping={"a0": "p0", "a1": "p0"},
                                 seed=0)
    from ray_tpu.rllib import models
    import jax

    params = models.init_policy_params(jax.random.PRNGKey(0), 4, 2, 32)
    runner.set_weights({"p0": params})
    batch = runner.sample()
    assert set(batch) == {"a0", "a1"}
    for a in ("a0", "a1"):
        assert batch[a]["obs"].shape == (8, 4, 4)
        assert batch[a]["actions"].shape == (8, 4)
        assert batch[a]["last_value"].shape == (4,)
    metrics = runner.get_metrics()
    assert set(metrics) == {"a0", "a1"}


def test_multi_agent_ppo_parameter_sharing_learns():
    """Two agents share one policy id: pooled experience, one learner.
    The shared policy must improve on CartPole (multi-agent learning
    gate; pooling doubles the batch so the budget stays small)."""
    def ctor(num_envs, seed):
        return IndependentEnsembleEnv(
            {"a0": "CartPole", "a1": "CartPole"}, num_envs=num_envs,
            seed=seed)

    algo = MultiAgentPPOConfig(
        env=ctor, policies=("shared",),
        policy_mapping={"a0": "shared", "a1": "shared"},
        num_env_runners=0, num_envs_per_runner=8, rollout_len=128,
        minibatch_size=512, seed=0).build()
    best = 0.0
    for _ in range(40):
        result = algo.train()
        rets = [m["episode_return_mean"]
                for m in result["env_runners"].values()
                if m["episode_return_mean"] is not None]
        if rets:
            best = max(best, float(np.mean(rets)))
        if best >= 100.0:
            break
    assert best >= 100.0, f"shared policy failed to reach 100 (best {best})"
    assert set(algo.get_weights()) == {"shared"}
