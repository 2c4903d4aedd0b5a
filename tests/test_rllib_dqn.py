"""DQN + replay buffers + LearnerGroup + actor collectives (reference
test model: rllib DQN tuned_examples learning gates,
util/collective tests, learner_group multi-learner tests)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import DQNConfig, DQNLearner, LearnerGroup
from ray_tpu.rllib.replay_buffers import (PrioritizedReplayBuffer,
                                          ReplayBuffer)


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


# ------------------------------------------------------------ replay buffer

def test_replay_buffer_ring_and_sample():
    buf = ReplayBuffer(100, obs_size=3, seed=0)
    for start in range(0, 260, 20):
        n = 20
        buf.add_batch(np.full((n, 3), start, np.float32),
                      np.arange(n, dtype=np.int32) % 2,
                      np.ones(n, np.float32),
                      np.full((n, 3), start + 1, np.float32),
                      np.zeros(n, np.float32))
    assert len(buf) == 100  # ring capped
    s = buf.sample(32)
    assert s["obs"].shape == (32, 3)
    # Ring overwrote the oldest: only the last 100 rows' markers remain.
    assert s["obs"].min() >= 160


def test_prioritized_buffer_biases_sampling():
    buf = PrioritizedReplayBuffer(64, obs_size=1, alpha=1.0, seed=0)
    buf.add_batch(np.zeros((64, 1), np.float32),
                  np.zeros(64, np.int32), np.zeros(64, np.float32),
                  np.zeros((64, 1), np.float32), np.zeros(64, np.float32))
    # Give index 7 a huge priority; it must dominate samples.
    buf.update_priorities(np.arange(64), np.full(64, 1e-3))
    buf.update_priorities(np.array([7]), np.array([100.0]))
    s = buf.sample(512)
    frac = float((s["indices"] == 7).mean())
    assert frac > 0.5, frac
    assert s["weights"].shape == (512,)


# ----------------------------------------------------------------- learner

def test_dqn_learner_reduces_td_error():
    rng = np.random.default_rng(0)
    learner = DQNLearner(4, 2, lr=5e-3, target_update_freq=10, seed=0)
    batch = {
        "obs": rng.normal(size=(256, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, 256).astype(np.int32),
        "rewards": rng.normal(size=256).astype(np.float32),
        "next_obs": rng.normal(size=(256, 4)).astype(np.float32),
        "dones": (rng.random(256) < 0.1).astype(np.float32),
    }
    first = learner.update_from_batch(batch)["loss"]
    for _ in range(50):
        last = learner.update_from_batch(batch)["loss"]
    assert last < first, (first, last)


def test_dqn_cartpole_learning_gate():
    """Second learning-regression gate in the suite (VERDICT item 7):
    CartPole mean return >= 130 within a bounded budget."""
    algo = (DQNConfig()
            .environment("CartPole")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=8,
                         rollout_fragment_length=32)
            .training(lr=1e-3, train_batch_size=64,
                      target_network_update_freq=250,
                      num_steps_sampled_before_learning_starts=1000,
                      updates_per_iteration=32)
            .build())
    best = 0.0
    try:
        for _ in range(120):
            result = algo.train()
            ret = result["env_runners"]["episode_return_mean"]
            if ret is not None:
                best = max(best, ret)
            if best >= 130.0:
                break
    finally:
        algo.stop()
    assert best >= 130.0, f"DQN failed to reach 130 on CartPole ({best})"


# ------------------------------------------------------------- collectives

def test_collective_allreduce_allgather_8_actors(cluster):
    from ray_tpu.util import collective as col

    @ray_tpu.remote(num_cpus=0)
    class Rank:
        def __init__(self, rank, world):
            col.init_collective_group(world, rank, "test-gang")
            self.rank = rank
            self.world = world

        def run(self):
            out = col.allreduce(np.full(4, self.rank + 1.0), "test-gang")
            gathered = col.allgather(np.array([self.rank]), "test-gang")
            col.barrier("test-gang")
            chunk = col.reducescatter(np.arange(8.0), "test-gang")
            b = col.broadcast(
                np.array([42.0]) if self.rank == 3 else None,
                root=3, group_name="test-gang")
            return (out.tolist(), [g.tolist() for g in gathered],
                    chunk.tolist(), b.tolist())

    world = 8
    ranks = [Rank.remote(i, world) for i in range(world)]
    results = ray_tpu.get([r.run.remote() for r in ranks], timeout=120)
    expected_sum = float(sum(range(1, world + 1)))
    for rank, (red, gathered, chunk, b) in enumerate(results):
        assert red == [expected_sum] * 4
        assert gathered == [[i] for i in range(world)]
        assert chunk == [float(rank) * world]  # sum of 8 copies, split
        assert b == [42.0]
    for r in ranks:
        ray_tpu.kill(r)
    # The named coordinator must not outlive the gang in the shared
    # module cluster (a stale world_size poisons later groups).
    ray_tpu.kill(ray_tpu.get_actor("rtpu-collective-test-gang"))


def test_learner_group_multi_learner_matches_single(cluster):
    """2-learner DDP update == single-learner update on the same batch
    (mean gradient over shards == full-batch gradient when shards are
    equal halves)."""
    rng = np.random.default_rng(1)
    batch = {
        "obs": rng.normal(size=(128, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, 128).astype(np.int32),
        "rewards": rng.normal(size=128).astype(np.float32),
        "next_obs": rng.normal(size=(128, 4)).astype(np.float32),
        "dones": np.zeros(128, np.float32),
    }

    def factory():
        return DQNLearner(4, 2, lr=1e-3, target_update_freq=1000, seed=7)

    single = LearnerGroup(factory, num_learners=0)
    multi = LearnerGroup(factory, num_learners=2,
                         group_name="lg-test")
    try:
        s1 = single.update_from_batch(dict(batch))
        s2 = multi.update_from_batch(dict(batch))
        assert "loss" in s1 and "loss" in s2
        w1 = single.get_weights()
        w2 = multi.get_weights()
        import jax

        for a, b in zip(jax.tree_util.tree_leaves(w1),
                        jax.tree_util.tree_leaves(w2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        assert len(s2["td_errors"]) == 128
    finally:
        multi.stop()


def test_dqn_multi_learner_trains(cluster):
    """DQN through the 2-learner group still learns (short smoke: loss
    decreases and returns improve over the random baseline)."""
    algo = (DQNConfig()
            .environment("CartPole")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                         rollout_fragment_length=32)
            .training(lr=1e-3, train_batch_size=64,
                      num_steps_sampled_before_learning_starts=500,
                      updates_per_iteration=32)
            .learners(num_learners=2)
            .build())
    best = 0.0
    try:
        for _ in range(45):
            result = algo.train()
            ret = result["env_runners"]["episode_return_mean"]
            if ret is not None:
                best = max(best, ret)
            if best >= 40.0:
                break
    finally:
        algo.stop()
    assert best >= 40.0, best
