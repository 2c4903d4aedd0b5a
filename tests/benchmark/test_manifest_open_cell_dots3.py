"""The open manifest's eighth cell, `dots3.longdoc.flood`: its per-layer
readers on a run made by hand: what each counts, that a program without
the counters or the kernels (the parent of the PR that added them)
reads nothing and does not raise, and that every OTHER cell's run reads
nothing of them (each reader returns a number here and None elsewhere).

**Why the file has this name.** Tier-1 runs under ``xdist --dist load``,
which hands each worker a run of CONSECUTIVE tests in collection order
(a 24th of the suite at first). `test_manifest_open.py` and
`test_manifest_open_family.py` each run the whole of ``tests/benchmark``
on a copy, ten minutes apiece, and sort side by side: one worker got
both, and the suite's wall time was their sum while five workers stood
idle (PR 41: 1,288 s of the 1,470 allowed, 4,358 s of tests on six
workers; with an eighth cell's rehearsals inside both copies the run
was cut at its limit). This file's seventy quick tests sort BETWEEN the
two, so they fall to different workers (simulated from the recorded
durations: about 900 s; ROADMAP D13 has the numbers). The next
`benchmark` PR drops both copies' files (B1) and may rename this one."""

import json

import pytest

from benchmark.harness import manifest, opcount_dots3, peaks

CELL = "dots3.longdoc.flood"
METRICS = ["dsa_rows_read_pct", "moe_held_pairs_pct",
           "dsa_select_ms_per_step", "dsa_select_roofline",
           "dsa_decode_attn_ms_per_step", "dsa_decode_attn_roofline",
           "swa_decode_attn_ms_per_step", "dots3_step_rest_ms"]
COUNTERS = ("dsa_rows_visible", "dsa_rows_attended", "dsa_rows_selected",
            "moe_pairs_routed", "moe_pairs_held")


def _run():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    eng = config["driver_args"]["engine"]
    # A traced stretch of 10 chunks of 8 steps: 16 live slots of 12,288
    # rows, 2 full layers (each query chooses 2,048), 3 sliding layers,
    # 4 expert layers of which an eighth of the pairs is held; a step
    # of 9 ms: 2 x 150 us choosing, 2 x 500 us of full attention, 3 x
    # 100 us of window attention, 4 x 3 x 250 us of grouped products.
    steps, slots = 10 * eng["decode_chunk"], eng["max_batch"]
    stats = lambda k: {
        "decode_host_syncs": 10 * k,
        "dsa_rows_visible": 2 * slots * 12288 * steps * k,
        "dsa_rows_attended": 2 * slots * 12288 * steps * k,
        "dsa_rows_selected": 2 * slots * 2048 * steps * k,
        "moe_pairs_routed": 4 * slots * 8 * steps * k,
        "moe_pairs_held": 4 * slots * steps * k,
        # of the 32 held experts of each of 4 layers, 12 hit a step
        "moe_layer_steps": 4 * steps * k,
        "moe_expert_hits": 4 * 12 * steps * k}
    call = "{} custom-call bf16 tpu_custom_call"
    product = "ragged-dot-none{} custom-call bf16[128,1536] tpu_custom_call"
    ops = {call.format("rtpu_dsa_select.2"): steps * 2 * 150e-6,
           call.format("rtpu_dsa_decode_attention.4"): steps * 2 * 500e-6,
           call.format("rtpu_swa_decode_attention.6"): steps * 3 * 100e-6,
           "ragged-dot-none.9 custom-call bf16[16384,1536] "
           "tpu_custom_call": 0.7}
    ops.update({product.format(i): steps * 4 * 250e-6
                for i in ("", ".1", ".2")})
    return {"config": config, "peaks": peaks.of("TPU v5 lite"),
            "counters": {"trace_start": stats(1), "trace_end": stats(2),
                         "start": stats(1), "end": stats(2)},
            "trace": {"program_s": {"decode_chunk": [0.072] * 10},
                      "op_self_s": ops,
                      "op_count": {n: steps for n in ops}}}


def test_a_rows_bytes_at_the_published_sizes():
    config = _run()["config"]
    assert opcount_dots3.latent_row_bytes(config) == 1152
    assert opcount_dots3.window_row_bytes(config) == 2176
    assert opcount_dots3.index_key_bytes(config) == 256
    attn = opcount_dots3.dsa_decode_attention_cost(config, 2048)
    assert attn["bytes"] == 2048 * 1152
    assert attn["flops"] == 2.0 * 2048 * 128 * (576 + 512)
    select = opcount_dots3.dsa_select_cost(config, 12288)
    assert select["flops"] / select["bytes"] == 64      # memory-bound


def test_the_readers_count_what_the_program_counted():
    run, m = _run(), manifest.load()
    assert m.reader("dsa_rows_read_pct")(run) == pytest.approx(100.0)
    assert m.reader("moe_held_pairs_pct")(run) == pytest.approx(12.5)
    assert m.reader("dsa_select_ms_per_step")(run) == pytest.approx(0.3)
    assert m.reader("dsa_decode_attn_ms_per_step")(run) == pytest.approx(1.0)
    assert m.reader("swa_decode_attn_ms_per_step")(run) == pytest.approx(0.3)
    # The accepted reader of the grouped products, by its own rule
    # (rows = slots x experts a token = 128), and the step's rest.
    assert m.reader("moe_ms_per_step")(run) == pytest.approx(3.0)
    # ... and of the share of the HELD experts a step streams (the
    # file's `n_routed_experts` is the count held).
    assert m.reader("moe_experts_touched_pct")(run) == pytest.approx(37.5)
    assert CELL in m.metrics["moe_experts_touched_pct"]["workloads"]
    assert m.reader("dots3_step_rest_ms")(run) == pytest.approx(
        9.0 - 0.3 - 1.0 - 0.3 - 3.0)
    # 16 x 2,048 chosen rows a layer: 242 operations a byte, just past
    # the chip's ridge, so the operations bound it.
    rows = 16 * 2048
    want = max(rows * 1152 / 819e9,
               2.0 * rows * 128 * 1088 / 197e12) / 500e-6 * 100
    assert m.reader("dsa_decode_attn_roofline")(run) == pytest.approx(want)
    assert 8 < want < 11
    want = 16 * 12288 * 256 / 819e9 / 150e-6 * 100
    assert m.reader("dsa_select_roofline")(run) == pytest.approx(want)
    assert 35 < want < 45


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_them_reads_nothing(metric):
    """The parent's counters and trace: no such counter, no kernel."""
    run = _run()
    bare = json.loads(json.dumps(run))
    for snap in bare["counters"].values():
        for name in COUNTERS:
            snap.pop(name)
    bare["trace"]["op_self_s"] = {
        k: v for k, v in bare["trace"]["op_self_s"].items()
        if not k.startswith("rtpu_")}
    m = manifest.load()
    assert m.reader(metric)(bare) is None
    assert m.reader(metric)(dict(run, trace=None)) is None or (
        metric in METRICS[:2])


OTHER_CELLS = [c for c in manifest.load().cells if c != CELL]


@pytest.mark.parametrize("cell", OTHER_CELLS)
@pytest.mark.parametrize("metric", METRICS[2:])
def test_a_kernel_reader_reads_nothing_in_another_cell(metric, cell):
    """The same trace and counters under another cell's configuration
    and that cell's own kernels' names: None, and no error."""
    m = manifest.load()
    run = _run()
    run["config"] = m.config(m.cell(cell))
    if "engine" not in run["config"]["driver_args"]:     # a training cell
        run["trace"]["program_s"] = {"train_step": [2.0] * 3}
    run["trace"]["op_self_s"] = {
        k: v for k, v in run["trace"]["op_self_s"].items()
        if not k.startswith("rtpu_d") and not k.startswith("rtpu_s")}
    run["trace"]["op_self_s"].update({
        "rtpu_mla_decode_attention.3 custom-call bf16 tpu_custom_call": 0.1,
        "rtpu_decode_attention.3 custom-call bf16 tpu_custom_call": 0.1,
        "rtpu_sparse_decode_attention.3 custom-call bf16 "
        "tpu_custom_call": 0.1})
    assert m.reader(metric)(run) is None


@pytest.mark.parametrize("metric", METRICS[:2])
def test_a_counter_reader_reads_nothing_without_its_counters(metric):
    """Another family's `engine.stats()` has none of these counters."""
    run = _run()
    for snap in run["counters"].values():
        for name in COUNTERS:
            snap.pop(name)
    assert manifest.load().reader(metric)(run) is None


@pytest.mark.parametrize("metric", METRICS[2:])
def test_another_latent_family_reads_nothing(metric):
    """The GLM cell's trace holds the latent kernel under ITS name and
    grouped products too: these readers are this family's."""
    m = manifest.load()
    run = _run()
    run["trace"]["op_self_s"] = {
        "rtpu_mla_decode_attention.3 custom-call bf16 tpu_custom_call": 0.1,
        "ragged-dot-none custom-call bf16[128,1536] tpu_custom_call": 0.1}
    run["config"] = m.config(m.cell("glm47flash.code.flood"))
    assert m.reader(metric)(run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_cell_lists_them(metric):
    m = manifest.load()
    assert metric in {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    assert m.metrics[metric]["moves"] == "serve_tok_s"
    assert m.metrics[metric]["workloads"] == [CELL]


def test_the_cell_is_one_chip_under_the_mix_another_selecting_cell_has():
    m = manifest.load()
    cell = m.cell(CELL)
    assert cell["chips"] == 1
    assert cell["config"] == "dots3-note-prev-l5-ep8"
    assert cell["traffic"] == m.cell("minicpmsala.longdoc.flood")["traffic"]
    mix = m.traffic(cell)
    eng = m.config(cell)["driver_args"]["engine"]
    assert mix["kind"] == "closed" and mix["clients"] == 2 * eng["max_batch"]
    assert mix["prompt_len"]["min"] > 4 * m.config(cell)["index_topk"]
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] <= eng[
        "max_len"]
