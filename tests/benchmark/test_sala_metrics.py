"""The MiniCPM-SALA family's per-layer readers on a run made by hand:
what each counts, and that a program without the counters or the
kernels (the parent of the PR that added them) reads nothing and does
not raise."""

import json

import pytest

from benchmark.harness import manifest, opcount_sala, peaks

CELL = "minicpmsala.longdoc.flood"
METRICS = ["sparse_decode_attn_ms_per_step", "sparse_decode_attn_roofline",
           "sparse_rows_read_pct", "lightning_decode_ms_per_step",
           "lightning_decode_roofline", "sala_mechanism_share_pct"]
COUNTERS = ("sparse_rows_selected", "sparse_rows_held",
            "lightning_state_steps")


def _run():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A traced stretch of 10 chunks of 8 steps: 14 of the 16 slots live,
    # each holding 16,384 rows of which a selection reads 4,096, in the
    # 4 sparse layers; each stepped in the 12 lightning layers.
    steps, live = 10 * eng["decode_chunk"], 14
    stats = lambda k: {
        "decode_host_syncs": 10 * k,
        "sparse_rows_selected": steps * live * 4 * 4096 * k,
        "sparse_rows_held": steps * live * 4 * 16384 * k,
        "lightning_state_steps": steps * live * 12 * k}
    sparse = "rtpu_sparse_decode_attention.3 custom-call bf16 tpu_custom_call"
    state = "rtpu_lightning_decode.5 custom-call f32 tpu_custom_call"
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.128] * 10},
                      "busy_s": 4.0,
                      "op_self_s": {sparse: steps * 4 * 200e-6,
                                    state: steps * 12 * 100e-6},
                      "op_count": {sparse: steps * 4, state: steps * 12}}}, live


def test_rows_and_states_are_counted_at_their_published_sizes():
    config = _run()[0]["config"]
    assert opcount_sala.row_bytes(config) == 1024
    assert opcount_sala.state_bytes(config) == 2_097_152
    rows = opcount_sala.sparse_decode_cost(config, 4096)
    assert rows["bytes"] == 4096 * 1024
    assert rows["flops"] / rows["bytes"] == 16      # 16 query heads a KV head
    step = opcount_sala.lightning_decode_cost(config, 1)
    assert 2 * 2_097_152 < step["bytes"] < 2 * 2_097_152 * 1.02
    assert step["flops"] < step["bytes"]            # memory-bound


def test_the_readers_count_what_the_program_counted():
    run, live = _run()
    m = manifest.load()
    want = live * 4096 * 1024 / 819e9 / 200e-6 * 100
    assert m.reader("sparse_decode_attn_roofline")(run) == pytest.approx(want)
    assert 30 < want < 40
    assert m.reader("sparse_decode_attn_ms_per_step")(run) == pytest.approx(
        4 * 0.2)
    cost = opcount_sala.lightning_decode_cost(run["config"], live)
    want = cost["bytes"] / 819e9 / 100e-6 * 100
    assert m.reader("lightning_decode_roofline")(run) == pytest.approx(want)
    assert 70 < want < 75
    assert m.reader("lightning_decode_ms_per_step")(run) == pytest.approx(
        12 * 0.1)
    assert m.reader("sparse_rows_read_pct")(run) == pytest.approx(25.0)
    assert m.reader("sala_mechanism_share_pct")(run) == pytest.approx(
        80 * (4 * 200e-6 + 12 * 100e-6) / 4.0 * 100)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no such counter, no such kernel."""
    run, _ = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        for name in COUNTERS:
            snap.pop(name)
    bare["trace"]["op_self_s"] = bare["trace"]["op_count"] = {}
    assert manifest.load().reader(metric)(bare) is None
    untraced = dict(run, trace=None)
    assert manifest.load().reader(metric)(untraced) is None or (
        metric == "sparse_rows_read_pct")


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    assert m.metrics[metric]["moves"] == "serve_tok_s"
    assert m.metrics[metric]["workloads"] == [CELL]


def test_the_tick_simulation_follows_the_engines_plan_and_the_traffic():
    """`benchmark/tick_sim.py`: a prompt's chunks are the scheduler's,
    a seed's lengths are the traffic kind's, and a window of fixed
    program times completes more tokens when the prefill is faster."""
    from benchmark import tick_sim
    from ray_tpu.serve.engine.scheduler import bucket_for

    m = manifest.load()
    eng = m.config(m.cell(CELL))["driver_args"]["engine"]
    with open(manifest.ROOT / "benchmark" / "traffic"
              / "longdoc.flood.json") as f:
        mix = json.load(f)
    assert tick_sim.plan(9000, 2048, eng["prompt_buckets"]) == [
        2048] * 4 + [bucket_for(9000 - 4 * 2048, eng["prompt_buckets"])]
    assert tick_sim.plan(300, 0, [512, 1024]) == [512]
    pool = tick_sim.requests(mix, eng["max_len"], 7)
    assert len(pool) == mix["pool"]
    assert all(mix["prompt_len"]["min"] <= p <= mix["prompt_len"]["max"]
               and mix["answer_len"]["min"] <= a <= mix["answer_len"]["max"]
               for p, a in pool)
    run = lambda ms: tick_sim.window_tok_s(
        mix, eng, 7, ms_per_ktok=ms, chunk_ms=128.5, grow=0.012, window=45.0)
    assert run(68.5) == run(68.5) and 200 < run(140.0) < run(68.5) < 500
