"""`smollm2.sft.fsdp2tp2`'s two readers of the rotary embedding's kernel
(``rtpu_fused_qk_rope``, PR 63) on a run made by hand: what each divides
by what, that the share of the roofline is of the bytes that q and k
REQUIRE (unpadded bf16, read once and written once a call, a chip's
share) and cannot pass 100 for a kernel that moves at least those, that
a program without the kernel (the parent of the PR that added it) reads
nothing and does not raise, and that the flash kernels' reader and
these do not match each other's calls."""

import json

import pytest

from benchmark.harness import manifest, opcount_rope, peaks
from benchmark.metrics import flash_roofline, rope_ms_per_step

CELL = "smollm2.sft.fsdp2tp2"
METRICS = ["rope_ms_per_step", "rope_roofline"]
KERNEL = "rtpu_fused_qk_rope.3 custom-call bf16[32768,1024] tpu_custom_call"
BACKWARD = "rtpu_fused_qk_rope.2 custom-call bf16[32768,1024] tpu_custom_call"
FLASH = "flash_attention.1 custom-call bf16[16,16,2048,64] tpu_custom_call"
# Three traced steps of 24 layers: a forward and a backward call a layer.
STEPS, LAYERS = 3, 24
# A device's q and k of one layer: 16 sequences x 2,048 rows x 16 heads
# of 64 in bf16, 67 MB each, read and written: 268 MB a call.
CALL_BYTES = 2 * 2 * 16 * 2048 * 16 * 64 * 2
LEAST_S = CALL_BYTES / 819e9


def _config():
    return json.loads((manifest.BENCH_DIR / "configs" / "smollm2-1.7b.json")
                      .read_text())


def _train_run(call_seconds=None, flash_too=False):
    if call_seconds is None:
        call_seconds = 2 * LEAST_S
    calls = STEPS * LAYERS
    trace = {"window_s": 5.7, "busy_s": 5.69, "collective_exposed_s": 0.1,
             "op_self_s": {KERNEL: calls * call_seconds,
                           BACKWARD: calls * call_seconds,
                           "fusion.764 fusion bf16[16,1024,2048]": 0.43},
             "op_count": {KERNEL: calls, BACKWARD: calls,
                          "fusion.764 fusion bf16[16,1024,2048]": calls}}
    if flash_too:
        trace["op_self_s"][FLASH] = 0.17
        trace["op_count"][FLASH] = calls
    return {"steps": [1.9] * STEPS, "window_s": 5.7,
            "tokens_per_step": 65536, "config": _config(), "chips": 4,
            "traffic": {"batch": 32, "seq": 2048},
            "peaks": peaks.of("TPU v5 lite"), "trace": trace}


def test_the_required_bytes_are_q_and_k_once_each_way():
    cost = opcount_rope.qk_rope_cost(_config(), 32, 2048)
    assert cost == {"bytes": 4.0 * CALL_BYTES, "flops": 0.0}
    assert CALL_BYTES == 268_435_456
    assert LEAST_S == pytest.approx(0.328e-3, rel=0.01)
    # Grouped keys are charged at their own width.
    grouped = dict(_config(), num_key_value_heads=8)
    assert opcount_rope.qk_rope_cost(grouped, 32, 2048)["bytes"] == (
        4.0 * CALL_BYTES * (32 + 8) / 64)


def test_the_readers_divide_the_kernels_time_by_the_steps_and_the_bytes():
    m = manifest.load()
    run = _train_run(call_seconds=0.7e-3)
    # 48 calls a step at 0.7 ms.
    assert m.reader(METRICS[0])(run) == pytest.approx(48 * 0.7)
    assert m.reader(METRICS[1])(run) == pytest.approx(LEAST_S / 0.7e-3 * 100)
    assert m.reader(METRICS[1])(_train_run()) == pytest.approx(50.0)
    # At the bytes' own time the share is 100: no kernel that moves q
    # and k through HBM once each way reads more.
    assert m.reader(METRICS[1])(_train_run(LEAST_S)) == pytest.approx(100.0)


def test_the_flash_kernels_and_the_rope_kernel_are_read_apart():
    assert rope_ms_per_step.KERNEL.match(KERNEL)
    assert rope_ms_per_step.KERNEL.match(BACKWARD)
    assert not rope_ms_per_step.KERNEL.match(FLASH)
    assert not flash_roofline.KERNEL.match(KERNEL)
    assert not rope_ms_per_step.KERNEL.match(
        "fusion.12 fusion bf16[32768,1024]")
    m = manifest.load()
    alone, both = _train_run(), _train_run(flash_too=True)
    for metric in METRICS:
        assert m.reader(metric)(both) == pytest.approx(m.reader(metric)(alone))
    assert m.reader("flash_roofline")(alone) is None
    assert m.reader("flash_roofline")(both) is not None


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("missing", ["kernel", "trace", "steps", "peaks",
                                     "everything"])
def test_a_program_without_the_kernel_reads_nothing(metric, missing):
    """The parent's trace holds no such call (its rope is XLA fusions);
    an untraced run has no trace at all, a serving run no steps."""
    run = _train_run()
    if missing == "kernel":
        for table in run["trace"].values():
            if isinstance(table, dict):
                table.pop(KERNEL), table.pop(BACKWARD)
    elif missing == "trace":
        run["trace"] = None
    elif missing == "steps":
        del run["steps"]
    elif missing == "peaks":
        run["peaks"] = None
    else:
        run = {}
    got = manifest.load().reader(metric)(run)
    if (metric, missing) == (METRICS[0], "peaks"):
        assert got == pytest.approx(48 * 2 * LEAST_S * 1e3)
    elif (metric, missing) == (METRICS[1], "steps"):
        assert got == pytest.approx(50.0)
    else:
        assert got is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    entry = m.metrics[metric]
    assert entry["moves"] == "train_tok_s" and entry["workloads"] == [CELL]
    assert entry["layer"] == "kernels" and entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if metric.endswith("roofline") else "ms")
    assert entry["better"] == ("higher" if metric.endswith("roofline")
                               else "lower")
