"""TorchTrainer: torch-DDP (gloo) training on the gang substrate
(reference analog: python/ray/train/tests/test_torch_trainer.py — DDP
process-group setup + allreduce gradient equivalence)."""

import numpy as np
import pytest

pytest.importorskip("torch")

import ray_tpu
from ray_tpu.train import RunConfig, ScalingConfig, TorchTrainer
from ray_tpu.train.config import FailureConfig


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_torch_trainer_process_group_and_allreduce(cluster, tmp_path):
    """Every worker lands in ONE gloo process group; an allreduce across
    the gang yields the rank-sum — the DDP substrate works end-to-end."""
    def loop(config):
        import torch
        import torch.distributed as dist

        from ray_tpu import train

        ctx = train.get_context()
        assert dist.is_initialized()
        assert dist.get_world_size() == 3
        assert dist.get_rank() == ctx.get_world_rank()
        t = torch.tensor([float(dist.get_rank() + 1)])
        dist.all_reduce(t)
        train.report({"allreduce": float(t.item()),
                      "rank": dist.get_rank()})

    result = TorchTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=3),
        run_config=RunConfig(storage_path=str(tmp_path),
                             name="pg-test")).fit()
    assert result.error is None
    assert result.metrics["allreduce"] == 6.0  # 1+2+3


def test_torch_trainer_ddp_training_converges(cluster, tmp_path):
    """DDP linear regression across 2 workers: gradients sync (loss drops
    to ~0 and both replicas hold identical weights)."""
    def loop(config):
        import torch
        import torch.distributed as dist

        from ray_tpu import train
        from ray_tpu.train.torch import prepare_model

        torch.manual_seed(0)
        model = prepare_model(torch.nn.Linear(2, 1))
        opt = torch.optim.SGD(model.parameters(), lr=0.2)
        rank = dist.get_rank()
        g = torch.Generator().manual_seed(100 + rank)
        X = torch.randn(64, 2, generator=g)
        y = X @ torch.tensor([[2.0], [-3.0]]) + 1.0
        for _ in range(60):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(X), y)
            loss.backward()
            opt.step()
        w = model.module.weight.detach().numpy().ravel()
        b = float(model.module.bias.item())
        train.report({"loss": float(loss.item()), "w0": float(w[0]),
                      "w1": float(w[1]), "b": b})

    result = TorchTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path),
                             name="ddp-test")).fit()
    assert result.error is None
    m = result.metrics
    assert m["loss"] < 1e-2, m
    np.testing.assert_allclose([m["w0"], m["w1"], m["b"]],
                               [2.0, -3.0, 1.0], atol=0.15)


def test_prepare_data_loader_shards(cluster, tmp_path):
    def loop(config):
        import torch
        import torch.distributed as dist
        import torch.utils.data as tud

        from ray_tpu import train
        from ray_tpu.train.torch import prepare_data_loader

        ds = tud.TensorDataset(torch.arange(20).float())
        loader = prepare_data_loader(
            tud.DataLoader(ds, batch_size=5))
        seen = sorted(float(x) for batch in loader for x in batch[0])
        total = torch.tensor([len(seen)])
        dist.all_reduce(total)
        train.report({"n_local": len(seen), "n_total": int(total.item())})

    result = TorchTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path),
                             name="loader-test")).fit()
    assert result.error is None
    assert result.metrics["n_local"] == 10  # 20 rows over 2 ranks
    assert result.metrics["n_total"] == 20
