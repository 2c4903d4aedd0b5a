"""The looped cell's per-layer readers on a run made by hand: what each
counts, that a program without the counters (the parent of the PR that
added the family) reads nothing and does not raise, and what the
manifest and the configuration file say of the cell."""

import json

import pytest

from benchmark.harness import manifest, opcount_looped, peaks

CELL = "ouro26b.math.flood"
METRICS = ["looped_step_stream_roofline", "looped_passes_per_token",
           "looped_cache_share_pct", "looped_weight_copy_ms_per_step",
           "decode_step_ms.ouro", "engine_occupancy_pct.ouro",
           "admissions_ahead_pct.ouro"]
APPENDED = ["device_idle_pct.flood", "engine_host_share_pct.flood",
            "prefill_ms_per_ktok.flood", "decode_frozen_step_pct",
            "decode_attn_ms_per_step.flood", "setup_trace_lower_s",
            "setup_cache_load_s", "setup_compile_s",
            "setup_programs_requested", "setup_init_s", "setup_rest_s"]
LOOPED = METRICS[:4]


def _run(copied_s: float = 0.0):
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A stretch of 10 chunks of 8 steps of 32 ms, all 8 slots live at
    # 250 rows (two 128-row blocks streamed a slot an entry).
    steps = 10 * eng["decode_chunk"]
    stats = lambda k: {
        "chunk_steps_retired": steps * k, "decode_host_syncs": 10 * k,
        "decode_steps": steps * 8 * k, "loop_passes": steps * 4 * k,
        "loop_layer_steps": steps * 192 * k,
        "decode_attn_rows": steps * 8 * 250 * k,
        "decode_attn_rows_streamed": steps * 8 * 256 * k,
        "admissions_ahead": 3 * k, "prefix_hits": 0, "prefix_misses": 4 * k}
    ops = {"fusion.9 fusion f32[8,5632]": steps * 192 * 60e-6,
           "copy.140 copy bf16[48,2048,2048]": copied_s,
           "dynamic-slice.7 dynamic-slice bf16[1,2048,5632]": copied_s,
           # Neither a block's matrix nor moved: the cache's rows and an
           # activation.
           "copy.3 copy bf16[8,16,128]": 1.0,
           "dynamic-update-slice.2 dynamic-update-slice bf16[192,8,16,512,"
           "128]": 1.0}
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.256] * 10},
                      "busy_s": 4.0, "window_s": 4.1, "op_self_s": ops,
                      "op_count": {k: 1 for k in ops}}}, steps


def test_a_step_is_counted_from_the_files_own_keys():
    config = _run()[0]["config"]
    assert opcount_looped.block_bytes(config) == 102_760_448
    assert opcount_looped.head_bytes(config) == 201_326_592
    assert opcount_looped.row_bytes(config) == 8_192
    # A token's rows: 192 entries of 8,192 B = the issue's 1.5 MiB.
    assert 192 * opcount_looped.row_bytes(config) == 1_572_864
    assert (2048, 2048) in opcount_looped.weight_shapes(config)
    assert {(2048, 5632), (5632, 2048)} <= opcount_looped.weight_shapes(
        config)
    cost = opcount_looped.step_cost(config, 192, 8 * 256, 8)
    assert cost["rows_bytes"] == 8 * 256 * 192 * 8_192
    assert cost["bytes"] == (192 * 102_760_448 + 201_326_592
                             + cost["rows_bytes"])
    # The issue's reckoning: about 23 GB a step, memory-bound by far.
    assert 22.5e9 < cost["bytes"] < 23.5e9
    assert cost["flops"] / 197e12 < 0.05 * cost["bytes"] / 819e9


@pytest.mark.parametrize("copied_s", [0.0, 0.004])
def test_the_readers_count_what_they_say(copied_s):
    run, steps = _run(copied_s)
    m = manifest.load()
    read = lambda name: m.reader(name)(run)
    assert read("looped_passes_per_token") == 4.0
    cost = opcount_looped.step_cost(run["config"], 192, 8 * 256, 8)
    assert read("looped_cache_share_pct") == pytest.approx(
        cost["rows_bytes"] / cost["bytes"] * 100)
    assert 13 < read("looped_cache_share_pct") < 15
    # 23.2 GB in 32 ms: a reading under 100.
    assert read("looped_step_stream_roofline") == pytest.approx(
        cost["bytes"] / 819e9 / 0.032 * 100)
    assert 85 < read("looped_step_stream_roofline") < 100
    # Operations of a block's matrix shapes alone, stack or layer.
    assert read("looped_weight_copy_ms_per_step") == pytest.approx(
        2 * copied_s / steps * 1e3)
    assert read("decode_step_ms.ouro") == pytest.approx(32.0)
    assert read("engine_occupancy_pct.ouro") == pytest.approx(100.0)
    assert read("admissions_ahead_pct.ouro") == pytest.approx(75.0)


@pytest.mark.parametrize("metric", LOOPED)
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no ``loop_*`` counter."""
    run, _ = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        for key in ("loop_passes", "loop_layer_steps"):
            snap.pop(key)
    assert manifest.load().reader(metric)(bare) is None
    # The two counter readers need no trace; the other two read none
    # without it.
    untraced = manifest.load().reader(metric)(dict(run, trace=None))
    assert (untraced is None) == (metric in (
        "looped_step_stream_roofline", "looped_weight_copy_ms_per_step"))
    no_steps = json.loads(json.dumps(run))
    for snap in no_steps["counters"].values():
        snap["chunk_steps_retired"] = 0
    assert metric == "looped_weight_copy_ms_per_step" or (
        manifest.load().reader(metric)(no_steps) is None)


@pytest.mark.parametrize("metric", METRICS + APPENDED)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    assert m.metrics[metric]["moves"] in ("serve_tok_s", "setup_s")
    if metric in METRICS:
        assert m.metrics[metric]["workloads"] == [CELL]
    else:
        assert CELL in m.metrics[metric]["workloads"]
    assert CELL in m.metrics["serve_tok_s"]["workloads"]


def test_the_cell_and_its_configuration_are_as_published():
    """All 48 layers, every width, the whole vocabulary, four passes;
    one key reduced; the catalog's numbers under the catalog's keys."""
    m = manifest.load()
    entry = m.configs["ouro-2.6b"]
    assert entry["reduced"] == ["max_position_embeddings"]
    assert entry["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    c = m.config(m.cell(CELL))
    assert set(c["reduced"]) == {"max_position_embeddings"}
    assert (c["num_hidden_layers"], c["hidden_size"], c["vocab_size"],
            c["intermediate_size"]) == (48, 2048, 49152, 5632)
    assert (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"]) == (16, 16, 128)
    assert (c["total_ut_steps"], c["early_exit_threshold"]) == (4, 1)
    assert c["layer_types"] == ["full_attention"] * 48
    assert (c["rope_theta"], c["rms_norm_eps"], c["max_window_layers"]) == (
        1000000, 1e-6, 48)
    assert set(c["assumed"]) >= {"norm_layout", "norm_between_passes",
                                 "exit_gate", "per_pass_cache", "no_bias",
                                 "precision"}
    assert (c["driver"], c["builder"]) == ("serve_looped", "ouro")
    assert c["driver_args"]["engine"] == {
        "max_batch": 8, "max_len": 512, "prompt_buckets": [64, 128, 256],
        "decode_chunk": 8, "kv_fleet_min_prefix_blocks": -1}
    cell, mix = m.cell(CELL), m.traffic(m.cell(CELL))
    assert (cell["traffic"], cell["chips"]) == ("math.flood", 1)
    assert (mix["kind"], mix["clients"], mix["pool"], mix["deal_block"],
            mix["lead_in_s"]) == ("closed", 16, 256, 8, 16)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.5, "min": 32, "max": 192}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.4, "min": 64, "max": 320}
    # Prompt + answer fit a slot's rows; the rows are whole 128-row
    # blocks of the decode kernel.
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] <= 512
    from ray_tpu.ops.decode_attention import decode_block_rows
    assert decode_block_rows(512, 16, 128, 2) == 128 and 512 % 128 == 0
