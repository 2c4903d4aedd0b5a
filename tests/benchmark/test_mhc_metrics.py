"""The mHC family's per-layer readers on a run made by hand: what each
counts, and that a program without the counters or the kernels (the
parent of the PR that added them) reads nothing and does not raise."""

import json

import pytest

from benchmark.harness import manifest, opcount_mhc, peaks

CELL = "xing4.rag.flood"
METRICS = ["mhc_ms_per_step", "mhc_prefill_ms_per_ktok", "mhc_mix_roofline",
           "mhc_share_pct", "mhc_sinkhorn_err_max"]
# The grouped products' readers under this family's rule for telling a
# step from a prefill (both take the repo's kernels here).
GROUPED = ["xing_moe_ms_per_step", "xing_moe_stream_roofline",
           "xing_moe_prefill_ms_per_ktok", "xing_moe_prefill_gmm_roofline"]
# Accepted readers that count this family right as they stand, in whose
# lists Kimi's cell must stay LAST (`test_kda_metrics.py`, not this PR's
# to edit): the cell reports them through the same reader files under
# names of its own, `<base>.xing4`.
OWN_NAME = ["decode_step_ms", "engine_occupancy_pct",
            "admissions_ahead_pct", "mla_decode_attn_ms_per_step",
            "mla_decode_attn_roofline", "moe_prefill_load_max_over_mean"]
# Accepted readers whose lists gained the cell's name at their end.
APPENDED = ["device_idle_pct.flood", "engine_host_share_pct.flood",
            "prefill_ms_per_ktok.flood", "prefill_chunk_ms_per_ktok.flood",
            "decode_frozen_step_pct", "moe_experts_touched_pct",
            "setup_compile_s", "setup_init_s"]
PRE_STEP = ("rtpu_mhc_pre.3 custom-call (f32[32,3584], f32[32,128]) "
            "tpu_custom_call")
POST_STEP = "rtpu_mhc_post.3 custom-call f32[32,14336] tpu_custom_call"
PRE_FILL = ("rtpu_mhc_pre.9 custom-call (f32[1024,3584], f32[1024,128]) "
            "tpu_custom_call")
POST_FILL = "rtpu_mhc_post.9 custom-call f32[1024,14336] tpu_custom_call"
# The held experts' products: a step's 32 slots x 4 are 128 rows, a
# bucket of 1,024 tokens 4,096.
SWIGLU_STEP = ("rtpu_grouped_swiglu.5 custom-call bf16[128,1024] "
               "tpu_custom_call")
DOWN_STEP = "rtpu_grouped_matmul.5 custom-call bf16[128,3584] tpu_custom_call"
SWIGLU_FILL = ("rtpu_grouped_swiglu.7 custom-call bf16[4096,1024] "
               "tpu_custom_call")
DOWN_FILL = ("rtpu_grouped_matmul.7 custom-call bf16[4096,3584] "
             "tpu_custom_call")


def _run():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A traced stretch of 10 chunks of 8 steps, 30 of the 32 slots live,
    # each mixed in the 80 sub-layers (the kernels mix all 32); two
    # prefills of 900 real tokens in buckets of 1,024; every slot at
    # 1,000 rows; 7 of the 8 held experts touched a layer-step.
    steps, live, subs, moe = 10 * eng["decode_chunk"], 30, 80, 38
    stats = lambda k: {
        "decode_host_syncs": 10 * k, "decode_steps": steps * live * k,
        "mhc_step_rows": steps * live * subs * k,
        "mhc_prefill_rows": 1800 * subs * k,
        # The engine keeps the largest: it does not grow with the calls.
        "mhc_sinkhorn_err_max": 0.01 * k,
        "moe_prefill_expert_hits": 2 * moe * 8 * k,
        "moe_pairs_routed": (steps * 32 + 1800) * 4 * moe * k,
        "moe_pairs_held": (steps * 32 + 1800) * 4 * moe // 8 * k,
        "moe_layer_steps": steps * moe * k,
        "moe_expert_hits": steps * moe * 7 * k,
        "mla_decode_rows": steps * 32 * 1000 * k,
        "prefill_chunk_tokens": 1800 * k, "prefill_chunks_dispatched": 2 * k,
        "moe_prefill_load_max": 2 * moe * 90 * k,
        "moe_prefill_load_mean": 2 * moe * 900 * 4 / 64 * k}
    latent = ("rtpu_mla_decode_attention.2 custom-call bf16[32,32,512] "
              "tpu_custom_call")
    ops = {PRE_STEP: (steps * subs, 12e-6), POST_STEP: (steps * subs, 8e-6),
           PRE_FILL: (2 * subs, 150e-6), POST_FILL: (2 * subs, 250e-6),
           latent: (steps * 40, 100e-6),
           SWIGLU_STEP: (steps * moe, 140e-6), DOWN_STEP: (steps * moe, 70e-6),
           SWIGLU_FILL: (2 * moe, 200e-6), DOWN_FILL: (2 * moe, 100e-6)}
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.2] * 10},
                      "busy_s": 2.4, "window_s": 2.5,
                      "op_self_s": {k: n * s for k, (n, s) in ops.items()},
                      "op_count": {k: n for k, (n, s) in ops.items()}}}


def test_the_streams_are_counted_once_each_way():
    config = _run()["config"]
    assert opcount_mhc.maps_width(config) == 24
    assert opcount_mhc.stream_bytes(config) == 57_344
    assert opcount_mhc.phi_bytes(config) == 1_376_256
    cost = opcount_mhc.mix_cost(config, 1, 0)
    assert cost["bytes"] == 2 * 57_344 + 3584 * 4
    assert cost["flops"] == 2 * 4 * 3584 * (24 + 1 + 4 + 1)
    # Memory-bound on a chip of 240 operations a byte.
    assert cost["flops"] / cost["bytes"] < 8
    # 80 sub-layers a token: 10.3 MB each way through the streams.
    assert 80 * cost["bytes"] == pytest.approx(10.32e6, rel=1e-2)
    assert opcount_mhc.mix_cost(config, 0, 2)["bytes"] == 2 * 1_376_256


def test_the_readers_count_what_they_say():
    run = _run()
    m = manifest.load()
    read = lambda name: m.reader(name)(run)
    # The step's kernels by their 32 rows, the prefill's by theirs.
    assert read("mhc_ms_per_step") == pytest.approx(80 * 0.020)
    assert read("mhc_prefill_ms_per_ktok") == pytest.approx(
        2 * 80 * 0.4 / 1.8)
    seconds = 80 * 80 * 20e-6 + 2 * 80 * 400e-6
    pairs = (80 * 30 + 1800) * 80
    calls = 80 * 80 + 2 * 80
    want = ((pairs * (2 * 57_344 + 14_336) + calls * 1_376_256) / 819e9
            / seconds * 100)
    assert read("mhc_mix_roofline") == pytest.approx(want)
    assert 0 < want < 100
    assert read("mhc_share_pct") == pytest.approx(seconds / 2.4 * 100)
    # The largest of the engine's life at the window's end, as kept.
    assert read("mhc_sinkhorn_err_max") == 0.02
    # The accepted readers count this family right as they stand.
    assert read("decode_step_ms.xing4") == pytest.approx(25.0)
    assert read("mla_decode_attn_ms_per_step.xing4") == pytest.approx(
        40 * 0.1)
    assert 0 < read("mla_decode_attn_roofline.xing4") < 100
    assert read("moe_prefill_load_max_over_mean.xing4") == pytest.approx(
        90 / (900 * 4 / 64))
    # Held experts touched: of the 8 the file counts, not of the 64.
    assert read("moe_experts_touched_pct") == pytest.approx(7 / 8 * 100)


def test_idle_slots_and_two_passes_read_low_and_nothing_passes_100():
    """One pass over the streams at the memory's peak, every slot live,
    reads 100; the two kernels' five passes (X read twice and written
    once, x and y) read 64; half the slots live reads 62.5: ``Phi`` is
    a quarter of a 32-row call's bytes and is read whoever is live."""
    run = _run()
    c = run["config"]
    steps, subs = 80, 80
    for name in (PRE_FILL, POST_FILL):
        run["trace"]["op_self_s"].pop(name)
        run["trace"]["op_count"].pop(name)
    cost = opcount_mhc.mix_cost(c, 32, 1)
    two_kernels = cost["bytes"] + 32 * (57_344 + 2 * 14_336)
    reader = manifest.load().reader("mhc_mix_roofline")
    for moved, live, want in ((cost["bytes"], 32, 100.0),
                              (two_kernels, 32, None), (cost["bytes"], 16,
                                                        None)):
        run["trace"]["op_self_s"][PRE_STEP] = steps * subs * moved / 819e9
        run["trace"]["op_self_s"][POST_STEP] = 0.0
        for k, snap in ((1, "trace_start"), (2, "trace_end")):
            run["counters"][snap]["mhc_step_rows"] = steps * live * subs * k
            run["counters"][snap]["mhc_prefill_rows"] = 0
        got = reader(run)
        if want is not None:
            assert got == pytest.approx(want)
        elif live == 32:
            assert 60 < got < 70
        else:
            assert got == pytest.approx(62.5, abs=0.5)


def test_the_grouped_products_are_told_apart_by_their_rows():
    """The step's two kernels a layer by their 128 rows, a prefill's by
    any other; the step's share of its roofline from the touched held
    experts the program counted (7 of 8 a layer-step, 22.0 MB each),
    the prefill's from the 8 a layer each of the two prefills touched;
    the accepted readers of the same products read this cell WRONG
    (`moe_ms_per_step` nothing, `moe_prefill_ms_per_ktok` the step's
    calls too), which is why the cell has its own."""
    run = _run()
    m = manifest.load()
    read = lambda name: m.reader(name)(run)
    assert read("xing_moe_ms_per_step") == pytest.approx(38 * 0.210)
    assert read("xing_moe_prefill_ms_per_ktok") == pytest.approx(
        2 * 38 * 0.3 / 1.8)
    expert = 3 * 3584 * 1024 * 2
    assert read("xing_moe_stream_roofline") == pytest.approx(
        80 * 38 * 7 * expert / 819e9 / (80 * 38 * 210e-6) * 100)
    assert read("xing_moe_prefill_gmm_roofline") == pytest.approx(
        2 * 38 * 8 * expert / 819e9 / (2 * 38 * 300e-6) * 100)
    assert 0 < read("xing_moe_stream_roofline") < 100
    assert 0 < read("xing_moe_prefill_gmm_roofline") < 100
    assert read("moe_ms_per_step") is None
    assert read("moe_prefill_ms_per_ktok") > 10 * read(
        "xing_moe_prefill_ms_per_ktok")
    for name in ("moe_ms_per_step", "moe_prefill_ms_per_ktok",
                 "moe_prefill_gmm_roofline"):
        assert CELL not in m.metrics[name]["workloads"]


@pytest.mark.parametrize("metric", METRICS + GROUPED)
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no such counter, no such
    kernel."""
    run = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        for key in ("mhc_step_rows", "mhc_prefill_rows",
                    "mhc_sinkhorn_err_max", "moe_expert_hits",
                    "moe_layer_steps", "moe_prefill_expert_hits",
                    "moe_pairs_held", "moe_pairs_routed"):
            snap.pop(key)
    bare["trace"]["op_self_s"] = bare["trace"]["op_count"] = {}
    assert manifest.load().reader(metric)(bare) is None
    # The counter reader needs no trace.
    assert manifest.load().reader(metric)(dict(run, trace=None)) is None \
        or metric == "mhc_sinkhorn_err_max"


@pytest.mark.parametrize("metric", METRICS + GROUPED + APPENDED)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    kind = "per_layer"
    assert metric in {x["name"] for x in m.metrics_of(CELL, kind)}
    moves = "setup_s" if metric.startswith("setup_") else "serve_tok_s"
    assert m.metrics[metric]["moves"] == moves
    if metric in APPENDED:
        assert CELL in m.metrics[metric]["workloads"]
    else:
        assert m.metrics[metric]["workloads"] == [CELL]


@pytest.mark.parametrize("base", OWN_NAME)
def test_a_pinned_list_is_left_alone_and_the_reader_is_shared(base):
    """The accepted entry does not name the cell; `<base>.xing4` does, is
    the accepted entry in every other key, and has no file of its own,
    so `manifest.reader` falls back to the accepted reader's file."""
    m = manifest.load()
    accepted = next(n for n in (base + ".flood", base) if n in m.metrics)
    own = m.metrics[base + ".xing4"]
    assert CELL not in m.metrics[accepted]["workloads"]
    assert own["workloads"] == [CELL]
    assert {k: v for k, v in own.items() if k not in ("name", "workloads")} \
        == {k: v for k, v in m.metrics[accepted].items()
            if k not in ("name", "workloads")}
    assert not (m.bench_dir / "metrics" / f"{base}.xing4.py").exists()
    run = _run()
    assert m.reader(base + ".xing4")(run) == m.reader(accepted)(run)


def test_the_cell_and_its_configuration_are_as_published():
    """The whole depth, every width, the router's 64 outputs and 4 a
    token, the residual's and YaRN's keys; four keys reduced; the
    catalog's numbers under the catalog's keys."""
    m = manifest.load()
    entry = m.configs["xing4.0-29b-a4b-ep8"]
    assert entry["reduced"] == ["n_routed_experts", "vocab_size",
                                "max_position_embeddings",
                                "num_nextn_predict_layers"]
    c = m.config(m.cell(CELL))
    assert set(c["reduced"]) == set(entry["reduced"])
    assert (c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]) == (
        40, 3584, 16384)
    assert (c["n_routed_experts"], c["reduced"]["n_routed_experts"]["source"],
            c["num_experts_per_tok"], c["routed_scaling_factor"]) == (
        8, 64, 4, 2)
    assert (c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"],
            c["num_attention_heads"]) == (768, 512, 128, 64, 128, 32)
    assert (c["intermediate_size"], c["moe_intermediate_size"],
            c["first_k_dense_replace"], c["n_shared_experts"]) == (
        9216, 1024, 2, 1)
    assert (c["hc_mult"], c["hc_sinkhorn_iters"], c["hc_eps"],
            c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]) == (
        4, 20, 1e-6, -30, 30)
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (c["rope_theta"], c["rms_norm_eps"], c["max_position_embeddings"],
            c["num_nextn_predict_layers"]) == (10000, 1e-6, 2048, 0)
    assert c["expert_parallel"] == {"chips": 8, "this_chip": 0}
    assert set(c["assumed"]) >= {"stream_ends", "hc_eps", "map_norm",
                                 "precision", "mhc_parameters", "rotary",
                                 "routing", "eos"}
    assert (c["driver"], c["builder"]) == ("serve_routed_mhc", "xing_mhc")
    assert c["driver_args"]["engine"] == {
        "max_batch": 32, "max_len": 2048,
        "prompt_buckets": [512, 1024, 2048], "decode_chunk": 8,
        "kv_fleet_min_prefix_blocks": -1}
    assert m.cell(CELL)["traffic"] == "rag.flood"
    assert m.cell(CELL)["chips"] == 1
    assert len(m.cell(CELL)["why"]) <= 200
