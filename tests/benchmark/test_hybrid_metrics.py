"""The hybrid family's per-layer readers on a run made by hand: what
each counts, and that a program without the counter or the kernel (the
parent of the PR that added them) reads nothing and does not raise."""

import json

import pytest

from benchmark.harness import manifest, opcount_hybrid, peaks

CELL = "olmohybrid.rag.flood"
METRICS = ["gdn_decode_ms_per_step", "gdn_decode_roofline",
           "gdn_state_step_share_pct"]


def _run():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A traced stretch of 10 chunks of 8 steps: 30 of the 32 slots live,
    # each stepped in the 12 linear layers; the kernel moves all 32.
    steps, live, layers = 10 * eng["decode_chunk"], 30, 12
    stats = lambda k: {"decode_host_syncs": 10 * k,
                       "gdn_slot_steps": steps * live * layers * k}
    kernel = "rtpu_gdn_decode.7 custom-call f32 tpu_custom_call"
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.16] * 10},
                      "op_self_s": {kernel: steps * layers * 250e-6},
                      "op_count": {kernel: steps * layers}}}, live


def test_the_state_is_counted_unpadded_and_once_each_way():
    config = _run()[0]["config"]
    assert opcount_hybrid.state_bytes(config) == 2_211_840
    cost = opcount_hybrid.gdn_decode_cost(config, 1)
    assert 2 * 2_211_840 < cost["bytes"] < 2 * 2_211_840 * 1.02
    assert cost["flops"] < cost["bytes"]            # memory-bound


def test_the_roofline_share_counts_the_live_slots_states():
    run, live = _run()
    m = manifest.load()
    cost = opcount_hybrid.gdn_decode_cost(run["config"], live)
    want = cost["bytes"] / 819e9 / 250e-6 * 100
    assert m.reader("gdn_decode_roofline")(run) == pytest.approx(want)
    assert 60 < want < 70
    assert m.reader("gdn_decode_ms_per_step")(run) == pytest.approx(12 * 0.25)
    assert m.reader("gdn_state_step_share_pct")(run) == pytest.approx(
        12 * 0.25 / 20 * 100)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no such counter, no such kernel."""
    run, _ = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        snap.pop("gdn_slot_steps")
    assert manifest.load().reader(metric)(bare) is None or metric in (
        "gdn_decode_ms_per_step", "gdn_state_step_share_pct")
    bare["trace"]["op_self_s"] = bare["trace"]["op_count"] = {}
    assert manifest.load().reader(metric)(bare) is None
    untraced = dict(run, trace=None)
    assert manifest.load().reader(metric)(untraced) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    assert m.metrics[metric]["moves"] == "serve_tok_s"
