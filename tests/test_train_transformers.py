"""TransformersTrainer shim: a stock HF Trainer runs on the gang with
gang-wide DDP and report() forwarding (reference analog:
python/ray/train/huggingface/transformers tests)."""

import numpy as np
import pytest

import ray_tpu

transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_transformers_trainer_two_workers(cluster, tmp_path):
    from ray_tpu.train import ScalingConfig, TransformersTrainer

    out_dir = str(tmp_path / "hf-out")

    def loop(config):
        import torch
        from transformers import (Trainer, TrainingArguments)

        from ray_tpu.train.huggingface import (RayTrainReportCallback,
                                               prepare_trainer)

        class TinyRegressor(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.net = torch.nn.Linear(4, 1)

            def forward(self, x=None, labels=None):
                pred = self.net(x).squeeze(-1)
                loss = torch.nn.functional.mse_loss(pred, labels)
                return {"loss": loss, "logits": pred}

        class Ds(torch.utils.data.Dataset):
            def __len__(self):
                return 64

            def __getitem__(self, i):
                x = torch.randn(4, generator=torch.Generator()
                                .manual_seed(i))
                return {"x": x, "labels": x.sum()}

        args = TrainingArguments(
            output_dir=config["out_dir"],
            per_device_train_batch_size=8,
            max_steps=6,
            logging_steps=2,
            save_strategy="no",
            report_to=[],
            use_cpu=True,
        )
        trainer = Trainer(model=TinyRegressor(), args=args,
                          train_dataset=Ds())
        trainer = prepare_trainer(trainer)
        trainer.add_callback(RayTrainReportCallback())
        trainer.train()

    result = TransformersTrainer(
        loop, train_loop_config={"out_dir": out_dir},
        scaling_config=ScalingConfig(num_workers=2)).fit()
    # report() forwarded HF's logged metrics through the gang machinery
    # (HF's final log carries train_loss; step logs carry loss).
    assert result.metrics and "train_loss" in result.metrics
    assert np.isfinite(result.metrics["train_loss"])
    assert result.metrics["step"] == 6
