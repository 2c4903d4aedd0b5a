"""`dots3.longdoc.flood`'s two readers of the window layers' prefill
kernel (``rtpu_swa_prefill_attention``, PR 60) on a run made by hand:
what each divides by what, that the share of the roofline is of what the
tokens REQUIRE (513 rows each, whatever span the kernel multiplied),
that a program without the kernel (the parent of the PR that added it)
reads nothing and does not raise, and that neither kind of layer's
readers match the other's kernel."""

import json

import pytest

from benchmark.harness import manifest, opcount_dots3_swa_prefill
from benchmark.metrics import dsa_prefill_attn_ms_per_ktok as full_layers
from benchmark.metrics import swa_prefill_attn_ms_per_ktok as window_layers
from tests.benchmark.test_manifest_open_cell_dots3 import CELL, _run

METRICS = ["swa_prefill_attn_ms_per_ktok", "swa_prefill_attn_roofline"]
KERNEL = "rtpu_swa_prefill_attention.7 custom-call f32[1,2048,8192] " \
         "tpu_custom_call"
FULL = "rtpu_dsa_prefill_attention.4 custom-call f32[1,2048,16384] " \
       "tpu_custom_call"
# The traced stretch dispatches 20 chunks of 2,048 real tokens through
# 3 sliding layers: 60 calls.
CHUNKS, LAYERS = 20, 3
TRACED_TOKENS = CHUNKS * 2048


def _least_seconds(config):
    """What the chip needs at its peak for the stretch's tokens."""
    assert opcount_dots3_swa_prefill.swa_prefill_attention_cost(
        config, 1.0) == {"bytes": 0.0,
                         "flops": 2.0 * 513 * 64 * (256 + 128) * 3}
    return TRACED_TOKENS * 513 * 64 * 384 * 2.0 * 3 / 197e12


def _prefill_run(kernel_seconds=None, full_too=False):
    run = _run()
    c = run["counters"]
    for key, k in (("start", 1), ("end", 2), ("trace_start", 1),
                   ("trace_end", 1)):
        c[key].update({"prefill_chunk_tokens": 10 ** 6 * k})
    c["trace_end"]["prefill_chunk_tokens"] += TRACED_TOKENS
    if kernel_seconds is None:
        kernel_seconds = 4 * _least_seconds(run["config"])
    run["trace"]["op_self_s"][KERNEL] = kernel_seconds
    run["trace"]["op_count"][KERNEL] = LAYERS * CHUNKS
    if full_too:
        run["trace"]["op_self_s"][FULL] = 0.5
        run["trace"]["op_count"][FULL] = 2 * CHUNKS
    return run


def test_the_readers_divide_the_kernels_time_by_what_was_dispatched():
    m = manifest.load()
    run = _prefill_run(kernel_seconds=0.06144)
    assert m.reader(METRICS[0])(run) == pytest.approx(1.5)      # ms a ktok
    least = _least_seconds(run["config"])
    assert m.reader(METRICS[1])(run) == pytest.approx(least / 0.06144 * 100)
    # 513 rows x 64 heads x 384 columns a token a layer at the peak:
    # 0.26 ms a layer a chunk of 2,048 tokens.
    assert least / CHUNKS / LAYERS == pytest.approx(0.262e-3, rel=0.01)


def test_the_share_is_a_quarter_where_the_kernel_takes_four_times_the_least():
    m = manifest.load()
    assert m.reader(METRICS[1])(_prefill_run()) == pytest.approx(25.0)
    # Padding in the bucket is time and no token: a last chunk half
    # full halves neither the kernel's time nor, so, doubles the share.
    half = _prefill_run()
    half["counters"]["trace_end"]["prefill_chunk_tokens"] -= TRACED_TOKENS // 2
    assert m.reader(METRICS[1])(half) == pytest.approx(12.5)


def test_each_kind_of_layers_readers_read_their_own_kernel_alone():
    assert window_layers.KERNEL.match(KERNEL)
    assert not window_layers.KERNEL.match(FULL)
    assert not full_layers.KERNEL.match(KERNEL)
    assert not window_layers.KERNEL.match(
        "rtpu_swa_decode_attention.6 custom-call bf16 tpu_custom_call")
    m = manifest.load()
    alone, both = _prefill_run(), _prefill_run(full_too=True)
    for metric in METRICS:
        assert m.reader(metric)(both) == pytest.approx(m.reader(metric)(alone))
    assert m.reader("dsa_prefill_attn_ms_per_ktok")(alone) is None
    assert m.reader("dsa_prefill_attn_ms_per_ktok")(both) == pytest.approx(
        0.5 / TRACED_TOKENS * 1e6)
    only_full = _prefill_run(full_too=True)
    del only_full["trace"]["op_self_s"][KERNEL]
    del only_full["trace"]["op_count"][KERNEL]
    for metric in METRICS:
        assert m.reader(metric)(only_full) is None


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("missing", ["kernel", "trace", "chunk_tokens",
                                     "peaks", "everything"])
def test_a_program_without_them_reads_nothing(metric, missing):
    """The parent's trace holds no such call; an untraced run has no
    trace at all, a training run nothing."""
    run = json.loads(json.dumps(_prefill_run()))
    if missing == "kernel":
        del run["trace"]["op_self_s"][KERNEL]
    elif missing == "trace":
        run["trace"] = None
    elif missing == "chunk_tokens":
        for snap in run["counters"].values():
            snap.pop("prefill_chunk_tokens")
    elif missing == "peaks":
        run["peaks"] = None
    else:
        run = {}
    got = manifest.load().reader(metric)(run)
    if (metric, missing) == (METRICS[0], "peaks"):
        assert got == pytest.approx(4 * _least_seconds(run["config"])
                                    / TRACED_TOKENS * 1e6)
    else:
        assert got is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    entry = m.metrics[metric]
    assert entry["moves"] == "serve_tok_s" and entry["workloads"] == [CELL]
    assert entry["layer"] == "kernels" and entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if metric.endswith("roofline") else "ms/ktok")
    assert entry["better"] == ("higher" if metric.endswith("roofline")
                               else "lower")
