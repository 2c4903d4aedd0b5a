"""Model FLOP/s utilisation: tokens per second x the FLOPs forward and
backward REQUIRE per token (`opcount.train_flops_per_token`; remat's
recomputation not counted) over chips x the published peak."""

from benchmark.harness import opcount


def read(run):
    if run["peaks"] is None:
        return None
    rate = len(run["steps"]) * run["tokens_per_step"] / run["window_s"]
    need = opcount.train_flops_per_token(run["config"], run["traffic"]["seq"])
    return rate * need / (run["chips"] * run["peaks"]["flops"]) * 100.0
