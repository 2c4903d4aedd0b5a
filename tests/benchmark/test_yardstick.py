"""The yardstick's arithmetic: operation counts against the published
parameter counts and the program's own formula, peaks, and the metric
readers on a made run."""

import json

import pytest

from benchmark.builders import dense_llama
from benchmark.harness import manifest, opcount, peaks

M = manifest.load()


def _config(name):
    return json.loads((manifest.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def test_parameter_counts_are_the_published_ones():
    smol, mistral = _config("smollm2-1.7b"), _config("mistral-7b-v0.3-l16")
    assert opcount.param_count(smol) == 1_711_376_384          # "1.7B"
    assert opcount.param_count(dict(mistral, num_hidden_layers=32)) \
        == 7_248_023_552                                        # "7.25B"
    for c in (smol, mistral):
        cfg = dense_llama.config(c)
        assert opcount.param_count(c) == cfg.param_count()
        # The program's formula also counts the norm gains as matmul
        # parameters: a 5e-5 difference.
        assert opcount.train_flops_per_token(c, 2048) == pytest.approx(
            cfg.flops_per_token(2048), rel=1e-4)
    # No width of either configuration differs from its source.
    assert (mistral["hidden_size"], mistral["intermediate_size"],
            mistral["num_attention_heads"], mistral["num_key_value_heads"],
            mistral["vocab_size"]) == (4096, 14336, 32, 8, 32768)
    assert (smol["hidden_size"], smol["intermediate_size"],
            smol["num_attention_heads"], smol["num_key_value_heads"],
            smol["num_hidden_layers"], smol["vocab_size"]) \
        == (2048, 8192, 32, 32, 24, 49152)


def test_peaks_and_roofline():
    v5e = peaks.of("TPU v5 lite")
    assert (v5e["flops"], v5e["bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.of("TPU v9 imaginary")
    cost = opcount.flash_attention_cost(_config("smollm2-1.7b"), 32, 2048)
    assert cost["flops"] == 4 * 32 * 32 * 2048 * 2048 * 64 / 2
    assert cost["bytes"] == 4 * 32 * 32 * 2048 * 64 * 2
    # Compute-bound: 5.5e11 FLOP at 197e12 is 2.8 ms, 1.07 GB is 1.3 ms.
    assert opcount.roofline_seconds(cost, v5e) == cost["flops"] / 197e12


def _serve_run():
    recs = [{"due": float(i), "sent": i + 0.001, "first": i + 0.5,
             "last": i + 0.5 + 0.04 * 10, "n_got": 11, "timed": i < 9,
             "token_times": [i + 0.5 + 0.04 * k for k in range(11)]}
            for i in range(10)]
    stats = lambda syncs, steps, toks, pre: {
        "decode_host_syncs": syncs, "decode_steps": steps,
        "tokens_generated": toks, "prefill_tokens": pre}
    return {"requests": recs, "window_s": 10.0, "setup_s": 21.5,
            "config": _config("mistral-7b-v0.3-l16"), "peaks": None,
            "counters": {"start": stats(100, 5000, 1000, 400),
                         "end": stats(150, 15240, 3000, 2400),
                         "trace_start": stats(90, 4000, 800, 300),
                         "trace_end": stats(140, 14000, 2800, 2300)},
            "trace": {"window_s": 5.0, "busy_s": 4.0,
                      "program_s": {"decode_chunk": [0.256, 0.264],
                                    "prefill": [0.1, 0.3]}}}


@pytest.mark.parametrize("metric, want", [
    ("setup_s", 21.5), ("ttft_p50_ms", 500.0), ("ttft_p90_ms", 500.0),
    ("tpot_p50_ms", 40.0), ("tpot_tail_p90_ms", 40.0),
    ("serve_tok_s", 11.0), ("gen_late_p95_ms", 1.0),
    ("engine_syncs_per_ktok", 25.0),
    ("engine_occupancy_pct", 10240 / (50 * 8 * 32) * 100),
    ("decode_step_ms", 32.5), ("decode_step_ms.flood", 32.5),
    ("prefill_ms_per_ktok", 200.0), ("device_idle_pct.steady", 20.0),
])
def test_serving_readers(metric, want):
    assert M.reader(metric)(_serve_run()) == pytest.approx(want)


def test_training_readers_and_absent_traces():
    smol = _config("smollm2-1.7b")
    run = {"steps": [2.0, 3.0, 2.5, 2.5], "window_s": 10.0,
           "tokens_per_step": 65536, "config": smol, "chips": 4,
           "traffic": {"batch": 32, "seq": 2048},
           "peaks": peaks.of("TPU v5e"), "trace": None}
    assert M.reader("train_tok_s")(run) == pytest.approx(26214.4)
    assert M.reader("train_step_p50_ms")(run) == pytest.approx(2500.0)
    assert M.reader("train_mfu_pct")(run) == pytest.approx(
        26214.4 * opcount.train_flops_per_token(smol, 2048)
        / (4 * 197e12) * 100)
    # A reader that finds nothing to read returns nothing.
    for name in ("collective_exposed_pct", "device_idle_pct.train",
                 "flash_roofline", "decode_step_ms"):
        assert M.reader(name)(run) is None
    kernel = "flash_attention.2 custom-call bf16[16,16,2048,64] tpu_custom_call"
    run["trace"] = {"window_s": 8.0, "busy_s": 7.8,
                    "collective_exposed_s": 1.2,
                    "op_self_s": {kernel: 0.2386, "fusion.1 fusion": 1.0},
                    "op_count": {kernel: 72, "fusion.1 fusion": 72}}
    # 72 calls x 0.698 ms at the roofline over 238.6 ms measured (PR 24).
    assert M.reader("flash_roofline")(run) == pytest.approx(21.05, abs=0.05)
    assert M.reader("collective_exposed_pct")(run) == pytest.approx(15.0)
    assert M.reader("device_idle_pct.train")(run) == pytest.approx(2.5)
    assert M.reader("train_mfu_pct")(dict(run, peaks=None)) is None
