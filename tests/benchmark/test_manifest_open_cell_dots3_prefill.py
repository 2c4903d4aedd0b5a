"""`dots3.longdoc.flood`'s two readers of the prefill kernel
(``rtpu_dsa_prefill_attention``, PR 43) on a run made by hand: what each
divides by what, that the share of the roofline cannot pass 100, and
that a program without the kernel or the counter (the parent of the PR
that added them) reads nothing and does not raise. The name keeps the
file beside `test_manifest_open_cell_dots3.py` in collection order (that
file's header has the reason)."""

import json

import pytest

from benchmark.harness import manifest, opcount_dots3_prefill
from tests.benchmark.test_manifest_open_cell_dots3 import CELL, _run

METRICS = ["dsa_prefill_attn_ms_per_ktok", "dsa_prefill_attn_roofline"]
KERNEL = "rtpu_dsa_prefill_attention.3 custom-call f32[1,2048,16384] " \
         "tpu_custom_call"
# A window of 8 prompts of 12,288 tokens, 2 full layers: each query
# keeps min(position + 1, 2,048) rows a layer; the traced stretch
# dispatches 20 chunks of 2,048 real tokens (3 1/3 prompts' worth).
PROMPT, PROMPTS, CHUNKS = 12288, 8, 20
PAIRS_A_PROMPT = 2 * (2048 * PROMPT - 2048 * 2047 // 2)
TRACED_TOKENS = CHUNKS * 2048


def _least_seconds(config):
    """What the chip needs at its peak for the stretch's pairs."""
    pairs = PAIRS_A_PROMPT / PROMPT * TRACED_TOKENS
    assert opcount_dots3_prefill.dsa_prefill_attention_cost(
        config, 1.0) == {"bytes": 0.0, "flops": 2.0 * 128 * (192 + 128)}
    return pairs * 2.0 * 128 * 320 / 197e12


def _prefill_run(kernel_seconds=None):
    """The decode cell's hand-made run with a prefill beside it; the
    kernel's time twice the least by default."""
    run = _run()
    c = run["counters"]
    for key, k in (("start", 1), ("end", 2), ("trace_start", 1),
                   ("trace_end", 1)):
        c[key].update({
            "prefill_tokens": PROMPT * PROMPTS * k,
            "dsa_prefill_rows_attended": PAIRS_A_PROMPT * PROMPTS * k,
            "prefill_chunk_tokens": 10 ** 6 * k,
            "prefill_chunks_dispatched": 500 * k})
    # The stretch itself: 20 chunks dispatched, and three whole prompts
    # came home in it (the counter's edge, which the reader steps round).
    c["trace_end"]["prefill_chunk_tokens"] += TRACED_TOKENS
    c["trace_end"]["prefill_chunks_dispatched"] += CHUNKS
    c["trace_end"]["prefill_tokens"] += 3 * PROMPT
    c["trace_end"]["dsa_prefill_rows_attended"] += 3 * PAIRS_A_PROMPT
    if kernel_seconds is None:
        kernel_seconds = 2 * _least_seconds(run["config"])
    run["trace"]["op_self_s"][KERNEL] = kernel_seconds
    run["trace"]["op_count"][KERNEL] = 2 * CHUNKS
    run["trace"]["program_s"]["prefill"] = [0.11] * CHUNKS
    return run


def test_the_readers_divide_the_kernels_time_by_what_was_dispatched():
    m = manifest.load()
    run = _prefill_run(kernel_seconds=0.8192)
    assert m.reader(METRICS[0])(run) == pytest.approx(
        0.8192 / TRACED_TOKENS * 1e6)                       # 20 ms a ktok
    least = _least_seconds(run["config"])
    assert m.reader(METRICS[1])(run) == pytest.approx(least / 0.8192 * 100)
    # 1,877 of 2,048 rows a token a layer at this length, at the peak:
    # 3.2 ms a chunk of 2,048 tokens.
    assert least / CHUNKS == pytest.approx(3.2e-3, rel=0.01)


def test_the_share_is_half_where_the_kernel_takes_twice_the_least():
    m = manifest.load()
    assert m.reader(METRICS[1])(_prefill_run()) == pytest.approx(50.0)
    # The decode cell's readers are not moved by the prefill's counter.
    assert m.reader("dsa_decode_attn_roofline")(_prefill_run()) == \
        pytest.approx(m.reader("dsa_decode_attn_roofline")(_run()))
    assert m.reader("dsa_rows_read_pct")(_prefill_run()) == pytest.approx(100)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("missing", ["kernel", "counters", "trace",
                                     "chunk_tokens"])
def test_a_program_without_them_reads_nothing(metric, missing):
    """The parent's trace holds no such call, its counters no such
    count; an untraced run has no trace at all."""
    run = json.loads(json.dumps(_prefill_run()))
    run["peaks"] = _prefill_run()["peaks"]
    if missing == "kernel":
        del run["trace"]["op_self_s"][KERNEL]
    elif missing == "trace":
        run["trace"] = None
    else:
        name = ("dsa_prefill_rows_attended" if missing == "counters"
                else "prefill_chunk_tokens")
        for snap in run["counters"].values():
            snap.pop(name)
    got = manifest.load().reader(metric)(run)
    if (metric, missing) == (METRICS[0], "counters"):
        assert got == pytest.approx(2 * _least_seconds(run["config"])
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
