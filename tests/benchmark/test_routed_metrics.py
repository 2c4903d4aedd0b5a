"""The routed family's per-layer readers on a run made by hand: what
each counts, and that a program without the counter or the kernel (the
parent of the PR that added them) reads nothing and does not raise."""

import json

import pytest

from benchmark.harness import manifest, opcount_routed, peaks

CELL = "glm47flash.code.flood"


def _run():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A traced stretch of 10 chunks of 8 steps: 30 of the 32 slots live
    # at 2,000 rows each, two parked on their last row.
    steps, live = 10 * eng["decode_chunk"], 30
    rows = steps * (live * 2000 + 2 * eng["max_len"])
    stats = lambda k: {
        "decode_host_syncs": 10 * k, "decode_steps": steps * live * k,
        "mla_decode_rows": rows * k, "moe_layer_steps": steps * 6 * k,
        "moe_expert_hits": steps * 6 * 56 * k,
        "moe_prefill_load_max": 400 * k, "moe_prefill_load_mean": 100.0 * k}
    layers = config["num_hidden_layers"]
    kernel = "rtpu_mla_decode_attention.17 custom-call bf16 tpu_custom_call"
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.1] * 10},
                      "op_self_s": {kernel: steps * layers * 170e-6},
                      "op_count": {kernel: steps * layers}}}, live


def test_the_roofline_share_counts_the_live_slots_rows():
    run, live = _run()
    read = manifest.load().reader("mla_decode_attn_roofline")
    cost = opcount_routed.mla_decode_attention_cost(
        run["config"], valid_rows=live * 2000, slots=32)
    want = cost["bytes"] / 819e9 / 170e-6 * 100
    assert read(run) == pytest.approx(want) and 40 < want < 60
    assert manifest.load().reader("mla_decode_attn_ms_per_step")(
        run) == pytest.approx(7 * 0.17)


@pytest.mark.parametrize("metric, want", [
    ("moe_experts_touched_pct", 56 / 64 * 100),
    ("moe_prefill_load_max_over_mean", 4.0)])
def test_the_expert_counters_readers(metric, want):
    run, _ = _run()
    assert manifest.load().reader(metric)(run) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "mla_decode_attn_roofline", "mla_decode_attn_ms_per_step",
    "moe_ms_per_step", "moe_experts_touched_pct",
    "moe_prefill_load_max_over_mean"])
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no such counter, no such kernel."""
    run, _ = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        for name in list(snap):
            if name.startswith(("mla_", "moe_")):
                del snap[name]
    bare["trace"]["op_self_s"] = bare["trace"]["op_count"] = {}
    assert manifest.load().reader(metric)(bare) is None
