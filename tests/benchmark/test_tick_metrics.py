"""The readers of PR 25's per-layer metrics on hand-made runs (numbers,
and ``None`` where a program lacks the counter or the operation), and
the reduction's choice between a request span and a tick span over one
idle gap."""

import pytest

from benchmark.harness import counters, manifest, trace_reduce as tr

M = manifest.load()


def _run(start, end, **more):
    return dict({"counters": {"start": start, "end": end}}, **more)


def test_delta_reads_nothing_where_a_counter_is_missing():
    run = _run({"requests": 3}, {"requests": 10, "queue_wait_s": 2.0})
    assert counters.delta(run, "requests") == 7
    assert counters.delta(run, "queue_wait_s") is None      # not at start
    assert counters.delta(run, "no_such") is None
    assert counters.delta({}, "requests") is None           # a train run
    assert counters.mean_ms(run, "queue_wait_s", "requests") is None


@pytest.mark.parametrize("metric, seconds_key, count_key", [
    ("engine_queue_wait_ms", "queue_wait_s", "requests"),
    ("engine_prefill_wait_ms", "prefill_wait_s", "requests"),
    ("front_first_deliver_ms", "first_deliver_s", "streams"),
])
def test_mean_wait_readers(metric, seconds_key, count_key):
    read = M.reader(metric)
    run = _run({seconds_key: 1.5, count_key: 10},
               {seconds_key: 31.5, count_key: 160})
    assert read(run) == pytest.approx(200.0)            # 30 s / 150
    # The parent commit's program has no such counter: nothing to read.
    assert read(_run({count_key: 10}, {count_key: 160})) is None
    # Nothing happened in the window.
    assert read(_run({seconds_key: 1.5, count_key: 10},
                     {seconds_key: 1.5, count_key: 10})) is None


@pytest.mark.parametrize("metric", ["engine_host_share_pct",
                                    "engine_host_share_pct.flood"])
def test_host_share_reader(metric):
    read = M.reader(metric)
    assert read.__code__.co_filename.endswith("engine_host_share_pct.py")
    zero = {f"tick_{k}_s": 0.0 for k in
            ("loop", "idle", "decode_fetch", "prefill_fetch")}
    end = {"tick_loop_s": 45.0, "tick_idle_s": 5.0,
           "tick_decode_fetch_s": 28.0, "tick_prefill_fetch_s": 6.0}
    # 40 s with work, 34 s of it in the fetches: 15 % elsewhere.
    assert read(_run(zero, end)) == pytest.approx(15.0)
    assert read(_run({}, {"decode_host_syncs": 9})) is None
    idle = dict(end, tick_idle_s=45.0)
    assert read(_run(zero, idle)) is None       # never had work


def _traced(op_self_s, programs, chunk=8):
    return {"trace": {"op_self_s": op_self_s, "program_s": programs},
            "config": {"driver_args": {"engine": {"decode_chunk": chunk}}}}


@pytest.mark.parametrize("name", [
    # called directly: XLA names the instruction for the kernel's scope
    "rtpu_decode_attention.3 custom-call bf16[32,8,4,128] tpu_custom_call",
    # under the engine's vmap over slots (v5e trace, PR 25)
    "closed_call.16 custom-call bf16[1,8,4,128] tpu_custom_call",
])
def test_decode_attention_reader_takes_the_kernel_under_both_names(name):
    read = M.reader("decode_attn_ms_per_step.flood")
    ops = {name: 0.232, "fusion.129 fusion bf16[32,14336]": 0.4,
           "custom-call.14 custom-call bf16[32,1,8,4,128] AllocateBuffer": 0.1,
           "flash_attention.2 custom-call bf16[1,32,256,128] tpu_custom_call":
               0.05}
    run = _traced(ops, {"decode_chunk": [0.27] * 16, "prefill": [0.03] * 30})
    assert read(run) == pytest.approx(0.232 / (16 * 8) * 1e3)


def test_decode_attention_reader_reads_nothing_without_kernel_or_program():
    read = M.reader("decode_attn_ms_per_step")
    ops = {"fusion.129 fusion bf16[32,14336]": 0.4}
    assert read(_traced(ops, {"decode_chunk": [0.27]})) is None
    kernel = {"closed_call.16 custom-call bf16[1,8,4,128] tpu_custom_call": .2}
    assert read(_traced(kernel, {"prefill": [0.03]})) is None
    assert read({"trace": None}) is None        # an untraced run


def test_new_metrics_sit_after_the_old_ones_in_their_cells():
    names = [m["name"] for m in M.data["per_layer"]]
    new = ["engine_queue_wait_ms", "engine_prefill_wait_ms",
           "front_first_deliver_ms", "engine_host_share_pct",
           "engine_host_share_pct.flood", "decode_attn_ms_per_step",
           "decode_attn_ms_per_step.flood"]
    # ... and before whatever a later PR appends.
    at = names.index("device_idle_pct.train") + 1
    assert names[at:at + len(new)] == new
    steady = {m["name"] for m in
              M.metrics_of("mistral7b.chat.steady", "per_layer")}
    flood = {m["name"] for m in
             M.metrics_of("mistral7b.chat.flood", "per_layer")}
    assert {n for n in new if not n.endswith(".flood")} <= steady
    assert {n for n in new if n.endswith(".flood")} <= flood
    assert not set(new) & {m["name"] for m in M.metrics_of(
        "smollm2.sft.fsdp2tp2", "per_layer")}


def _device(events):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events}]}]}


def test_a_gap_under_a_request_span_and_a_tick_span_goes_to_the_tick():
    """Device idle on [400, 800). The request's `engine.prefill` span
    covers all of it and more; the engine thread's
    `engine.tick.prefill_fetch` covers it too and is shorter: innermost
    wins. A gap that no tick span covers half of stays with the request
    span."""
    trace = _device([["fusion.1 fusion", 0, 400], ["fusion.2 fusion", 800, 100],
                     ["fusion.3 fusion", 1000, 100]])
    spans = [("engine.prefill", 100, 850),
             ("engine.queued", 0, 100),
             ("engine.tick.prefill_dispatch", 100, 390),
             ("engine.tick.prefill_fetch", 390, 820),
             ("engine.tick.prefill_deliver", 820, 850),
             ("engine.decode_chunk", 850, 1200)]
    idle = dict(tr.reduce(trace, 0, 1100, spans)["breakdown"]["idle_gaps"])
    assert idle == {"engine.tick.prefill_fetch": pytest.approx(400 / 1e9),
                    "engine.decode_chunk": pytest.approx(100 / 1e9)}
    # Without the tick spans (the parent commit) the same gap is the
    # request span's: the ledger's older lines.
    older = [s for s in spans if not s[0].startswith("engine.tick.")]
    idle = dict(tr.reduce(trace, 0, 1100, older)["breakdown"]["idle_gaps"])
    assert idle["engine.prefill"] == pytest.approx(400 / 1e9)
