"""The Mamba-2 hybrid family's per-layer readers on a run made by hand:
what each counts, and that a program without the counter or the kernel
(the parent of the PR that added them) reads nothing and does not
raise."""

import json

import pytest

from benchmark.harness import manifest, opcount_mamba2, peaks

CELL = "granite4hmicro.reason.flood"
METRICS = ["mamba2_decode_ms_per_step", "mamba2_decode_roofline",
           "mamba2_state_step_share_pct"]


def _run():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A traced stretch of 10 chunks of 8 steps: 60 of the 64 slots live,
    # each stepped in the 36 Mamba layers; the kernel moves all 64.
    steps, live, layers = 10 * eng["decode_chunk"], 60, 36
    stats = lambda k: {"decode_host_syncs": 10 * k,
                       "mamba2_slot_steps": steps * live * layers * k}
    kernel = "rtpu_mamba2_decode.7 custom-call f32 tpu_custom_call"
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.24] * 10},
                      "op_self_s": {kernel: steps * layers * 450e-6},
                      "op_count": {kernel: steps * layers}}}, live


def test_the_state_is_counted_unpadded_and_once_each_way():
    config = _run()[0]["config"]
    assert opcount_mamba2.state_bytes(config) == 2_097_152
    # 36 layers of it a slot: the 75.5 MB of the cell's name.
    assert 36 * opcount_mamba2.state_bytes(config) == 75_497_472
    cost = opcount_mamba2.mamba2_decode_cost(config, 1)
    assert 2 * 2_097_152 < cost["bytes"] < 2 * 2_097_152 * 1.02
    assert cost["flops"] == 5 * 64 * 64 * 128
    assert cost["flops"] < cost["bytes"]            # memory-bound


def test_the_roofline_share_counts_the_live_slots_states():
    run, live = _run()
    m = manifest.load()
    cost = opcount_mamba2.mamba2_decode_cost(run["config"], live)
    want = cost["bytes"] / 819e9 / 450e-6 * 100
    assert m.reader("mamba2_decode_roofline")(run) == pytest.approx(want)
    assert 65 < want < 72
    assert m.reader("mamba2_decode_ms_per_step")(run) == pytest.approx(
        36 * 0.45)
    assert m.reader("mamba2_state_step_share_pct")(run) == pytest.approx(
        36 * 0.45 / 30 * 100)


def test_idle_slots_moved_read_low_and_nothing_passes_100():
    """Every slot live and the kernel at the memory's peak reads 100;
    the same kernel time with half the slots live reads 50."""
    run, _ = _run()
    steps, layers = 80, 36
    cost = opcount_mamba2.mamba2_decode_cost(run["config"], 64)
    kernel = next(iter(run["trace"]["op_self_s"]))
    run["trace"]["op_self_s"][kernel] = steps * layers * cost["bytes"] / 819e9
    for live, want in ((64, 100.0), (32, 50.0)):
        for k, snap in ((1, "trace_start"), (2, "trace_end")):
            run["counters"][snap]["mamba2_slot_steps"] = (
                steps * live * layers * k)
        assert manifest.load().reader("mamba2_decode_roofline")(
            run) == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no such counter, no such kernel."""
    run, _ = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        snap.pop("mamba2_slot_steps")
    assert manifest.load().reader(metric)(bare) is None or metric in (
        "mamba2_decode_ms_per_step", "mamba2_state_step_share_pct")
    bare["trace"]["op_self_s"] = bare["trace"]["op_count"] = {}
    assert manifest.load().reader(metric)(bare) is None
    untraced = dict(run, trace=None)
    assert manifest.load().reader(metric)(untraced) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    assert m.metrics[metric]["moves"] == "serve_tok_s"
    assert m.metrics[metric]["workloads"] == [CELL]


def test_the_cell_and_its_configuration_are_as_published():
    """Depth, widths, vocabulary and multipliers uncut; one key reduced."""
    m = manifest.load()
    entry = m.configs["granite-4.0-h-micro"]
    assert entry["reduced"] == ["max_position_embeddings"]
    c = m.config(m.cell(CELL))
    assert set(c["reduced"]) == {"max_position_embeddings"}
    assert (c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]) == (
        40, 2048, 100352)
    assert c["layer_types"].count("mamba") == 36
    assert [i for i, k in enumerate(c["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert (c["embedding_multiplier"], c["residual_multiplier"],
            c["attention_multiplier"], c["logits_scaling"]) == (
        12, 0.22, 0.015625, 8)
    assert c["driver"] == "serve_hybrid" and c["builder"] == "granite_hybrid"
    assert m.cell(CELL)["traffic"] == "reason.flood"
