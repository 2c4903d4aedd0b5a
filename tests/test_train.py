"""Train-lite integration: worker gang, report lockstep, checkpoint/resume,
failure restart (SURVEY M6; reference test model:
python/ray/train/tests/test_data_parallel_trainer.py).

Runs against a real in-process cluster (worker subprocesses) with the tiny
Llama on CPU JAX — no TPU required.
"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (Checkpoint, CheckpointConfig, FailureConfig,
                           JaxTrainer, RunConfig, ScalingConfig)


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def _tiny_llama_loop(config):
    """Per-worker loop: trains tiny Llama, checkpoints pytrees, resumes."""
    import tempfile

    import jax
    import numpy as np

    import ray_tpu.train as train
    from ray_tpu.models import llama
    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh, mesh_context

    ctx = train.get_context()
    assert ctx.get_world_size() == config["world_size"]

    cfg = llama.tiny_config()
    mesh = make_mesh(MeshSpec(), jax.devices("cpu")[:1])
    tx = spmd.default_optimizer(lr=1e-2)
    with mesh_context(mesh):
        state = spmd.sharded_init(cfg, mesh, jax.random.PRNGKey(0), tx)
        start_step = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with ckpt.as_directory() as d:
                state = train.load_pytree(d)
                start_step = int(state.step)
        step_fn = spmd.make_train_step(cfg, mesh, tx)
        rng = np.random.default_rng(ctx.get_world_rank())
        for i in range(start_step, config["num_steps"]):
            tokens = rng.integers(0, cfg.vocab_size, (2, 64)).astype(np.int32)
            state, metrics = step_fn(state, tokens)
            if config.get("fail_at") == i and ckpt is None:
                raise RuntimeError("injected worker failure")
            payload = {"loss": float(metrics["loss"]), "step": i,
                       "start_step": start_step,
                       "rank": ctx.get_world_rank()}
            if (i + 1) % config["checkpoint_every"] == 0 \
                    and ctx.get_world_rank() == 0:
                d = tempfile.mkdtemp(prefix="rtpu_test_ckpt_")
                train.save_pytree(jax.device_get(state), d)
                train.report(payload, checkpoint=Checkpoint(d))
            else:
                train.report(payload)


def test_train_e2e_checkpoint_and_resume(cluster, tmp_path):
    run = RunConfig(name="tiny", storage_path=str(tmp_path),
                    checkpoint_config=CheckpointConfig(num_to_keep=2))
    trainer = JaxTrainer(
        _tiny_llama_loop,
        train_loop_config={"num_steps": 6, "checkpoint_every": 2,
                           "world_size": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=run,
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics is not None and result.metrics["step"] == 5
    assert result.checkpoint is not None
    assert len(result.metrics_dataframe) == 6          # 6 lockstep rounds
    # top-k retention: only 2 checkpoint dirs remain of the 3 registered
    ckpts = [n for n in os.listdir(result.path) if n.startswith("checkpoint_")]
    assert len(ckpts) == 2

    # Resume: new run, same storage -> starts from the saved step, not 0.
    trainer2 = JaxTrainer(
        _tiny_llama_loop,
        train_loop_config={"num_steps": 8, "checkpoint_every": 2,
                           "world_size": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=run,
    )
    result2 = trainer2.fit()
    assert result2.error is None
    # checkpoint_every=2, num_steps=6 -> latest checkpoint is post-step-5
    # (state.step == 6), so the resumed run reports starting there.
    assert result2.metrics["start_step"] == 6
    assert result2.metrics["step"] == 7


def test_train_failure_restarts_from_checkpoint(cluster, tmp_path):
    run = RunConfig(name="faulty", storage_path=str(tmp_path),
                    failure_config=FailureConfig(max_failures=1))
    trainer = JaxTrainer(
        _tiny_llama_loop,
        train_loop_config={"num_steps": 5, "checkpoint_every": 2,
                           "world_size": 1, "fail_at": 3},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=run,
    )
    result = trainer.fit()
    # Attempt 1 checkpoints after steps 1 and 3... fails AT step 3 before
    # reporting; attempt 2 resumes from the step-1 checkpoint (state.step=2)
    # and, now resuming (ckpt present), runs to completion.
    assert result.error is None
    assert result.metrics["step"] == 4
    assert result.metrics["start_step"] == 2


def test_train_failure_budget_exhausted(cluster, tmp_path):
    def always_fail(config):
        raise ValueError("boom")

    trainer = JaxTrainer(
        always_fail,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="doomed", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=0)),
    )
    result = trainer.fit()
    assert result.error is not None
    assert "boom" in str(result.error)


def test_worker_group_execute(cluster):
    from ray_tpu.train import WorkerGroup

    g = WorkerGroup(ScalingConfig(num_workers=2))
    g.start()
    try:
        outs = g.execute(lambda: os.getpid())
        assert len(outs) == 2 and outs[0] != outs[1]  # distinct processes
    finally:
        g.shutdown()
