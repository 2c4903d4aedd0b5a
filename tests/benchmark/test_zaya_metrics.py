"""The ZAYA1 cell's per-layer readers on a run made by hand: what each
counts, that a program without the counters or the products (the parent
of the PR that added them) reads nothing and does not raise, and that
the GLM cell's run, which has grouped products too, reads nothing of
them either."""

import json

import pytest

from benchmark.harness import manifest, opcount_zaya, peaks

CELL = "zaya1.reason.flood"
METRICS = ["top1_experts_touched_pct", "top1_decode_load_max_over_mean",
           "top1_moe_stream_roofline", "zaya_step_rest_ms"]
COUNTERS = ("moe_expert_hits", "moe_layer_steps", "moe_decode_load_max")


def _run():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A traced stretch of 10 chunks of 8 steps of 16 layers: 15.5 of the
    # 16 experts touched a layer-step, the largest group 9 of 64 tokens;
    # a layer's three grouped products 540 us together, its attention
    # kernel 60 us, the step 12.8 ms.
    steps, layers = 10 * eng["decode_chunk"], config["num_hidden_layers"]
    stats = lambda k: {
        "decode_host_syncs": 10 * k, "moe_layer_steps": steps * layers * k,
        "moe_expert_hits": int(steps * layers * 15.5) * k,
        "moe_decode_load_max": steps * layers * 9 * k}
    product = "ragged-dot-none{} custom-call bf16[64,2048] tpu_custom_call"
    prefill = "ragged-dot-none.7 custom-call bf16[512,2048] tpu_custom_call"
    attn = "rtpu_decode_attention.3 custom-call bf16 tpu_custom_call"
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.1024] * 10},
                      "op_self_s": {
                          product.format(""): steps * layers * 180e-6,
                          product.format(".1"): steps * layers * 180e-6,
                          product.format(".2"): steps * layers * 180e-6,
                          prefill: 0.5,
                          attn: steps * layers * 60e-6}}}


def test_an_experts_bytes_and_a_slots_tail_at_the_published_sizes():
    config = _run()["config"]
    assert opcount_zaya.expert_bytes(config) == 25_165_824
    assert opcount_zaya.row_bytes(config) == 1024
    assert opcount_zaya.tail_values(config) == 2688
    cost = opcount_zaya.grouped_decode_cost(config, 16, 64)
    assert cost["bytes"] == 16 * 25_165_824
    assert cost["flops"] / cost["bytes"] == 4           # memory-bound


def test_the_readers_count_what_the_program_counted():
    run = _run()
    m = manifest.load()
    assert m.reader("top1_experts_touched_pct")(run) == pytest.approx(
        15.5 / 16 * 100)
    assert m.reader("top1_decode_load_max_over_mean")(run) == pytest.approx(
        9 / 4)
    want = 15.5 * 25_165_824 / 819e9 / 540e-6 * 100
    assert m.reader("top1_moe_stream_roofline")(run) == pytest.approx(want)
    assert 85 < want < 90
    # The accepted reader of the same products, by its own rule (rows =
    # slots x experts a token), and the step's rest.
    assert m.reader("moe_ms_per_step")(run) == pytest.approx(16 * 0.54)
    assert m.reader("zaya_step_rest_ms")(run) == pytest.approx(
        12.8 - 16 * 0.54 - 16 * 0.06)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no such counter, no product."""
    run = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        for name in COUNTERS:
            snap.pop(name)
    bare["trace"]["op_self_s"] = {}
    assert manifest.load().reader(metric)(bare) is None
    untraced = dict(run, trace=None)
    assert manifest.load().reader(metric)(untraced) is None or (
        metric in METRICS[:2])


@pytest.mark.parametrize("metric", METRICS)
def test_another_routed_family_reads_nothing(metric):
    """The GLM cell's run has ``moe_expert_hits`` and grouped products
    too, under its own key for the experts' number: these readers are
    this family's."""
    m = manifest.load()
    run = dict(_run(), config=m.config(m.cell("glm47flash.code.flood")))
    assert m.reader(metric)(run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    assert m.metrics[metric]["moves"] == "serve_tok_s"
    assert m.metrics[metric]["workloads"] == [CELL]


def test_the_cell_is_one_chip_under_the_traffic_the_issue_gives():
    m = manifest.load()
    cell = m.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "zaya1-8b-l16"
    mix = m.traffic(cell)
    eng = m.config(cell)["driver_args"]["engine"]
    assert mix["kind"] == "closed" and mix["clients"] == 2 * eng["max_batch"]
    assert (mix["pool"], mix["deal_block"], mix["lead_in_s"]) == (1024, 8, 24)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 64, "max": 512}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.5, "min": 256, "max": 1536}
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] <= eng[
        "max_len"]
    assert "moe_experts_touched_pct" not in {
        x["name"] for x in m.metrics_of(CELL, "per_layer")}
