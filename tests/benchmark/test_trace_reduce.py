"""The reduction from a trace to numbers: interval arithmetic on a
hand-made trace with known answers, the recorded piece of a real v5e
trace, and the loader on a profile taken here."""

import gzip
import json
import pathlib

import pytest

from benchmark.harness import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"


def test_interval_arithmetic():
    u = tr.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert u == [[0, 3], [5, 8]] and tr.total(u) == 6
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 2], [4, 6]], [[1, 5]]) == [[0, 1], [5, 6]]
    assert tr.subtract([[0, 2]], []) == [[0, 2]]
    assert tr.clip([[0, 5], [8, 12]], 3, 10) == [[3, 5], [8, 10]]


def test_self_time_leaves_out_what_is_nested():
    ev = [["while.1 while", 0, 100], ["fusion.1 fusion", 10, 30],
          ["fusion.2 fusion", 50, 40], ["copy.1 copy", 120, 5]]
    own = {n: s for n, _, _, s in tr.self_times(ev)}
    assert own == {"while.1 while": 30, "fusion.1 fusion": 30,
                   "fusion.2 fusion": 40, "copy.1 copy": 5}


def test_short_names():
    text = ('%closed_call.16 = bf16[1,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} '
            'custom-call(s32[1]{0:T(128)} %b), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert tr.short_name(text) == ("closed_call.16 custom-call "
                                   "bf16[1,8,4,128] tpu_custom_call")
    assert tr.short_name("%while.8 = (s32[]{:T(128)}, bf16[2,3]{1,0}) "
                         "while((s32[]) %t), body=%b") == "while.8 while s32[]"
    assert tr.short_name("bench.anchor") == "bench.anchor"
    assert tr._program("jit_decode_chunk(133188807)") == "decode_chunk"


def _made_trace():
    """Two devices, window [0, 1000): device 0 busy 600 with one idle
    gap [400, 800); an all-reduce [300, 400) overlapped by compute on
    [300, 350) on device 0 and not at all on device 1."""
    ops0 = [["while.1 while", 0, 400], ["fusion.1 fusion", 0, 300],
            ["all-reduce.1 all-reduce", 300, 100],
            ["fusion.2 fusion", 800, 200]]
    ops0_async = [["fusion.9 fusion", 300, 50]]
    ops1 = [["fusion.1 fusion", 0, 300], ["all-reduce.1 all-reduce", 300, 100],
            ["fusion.2 fusion", 800, 100]]
    host = [["bench.window_begin", 0, 1], ["bench.window_end", 1000, 1],
            ["bench.anchor", -50, 1]]
    return {"planes": [
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": ops1}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops0 + ops0_async},
            {"name": "XLA Modules",
             "events": [["jit_decode_chunk(1)", 0, 400],
                        ["jit_prefill(2)", 800, 200],
                        ["jit_prefill(3)", 1200, 50]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


def test_reduce_on_a_made_trace():
    trace = _made_trace()
    lo, hi = tr.window_of(trace, "bench.window_begin", "bench.window_end")
    assert (lo, hi) == (0, 1000)
    spans = [("engine.decode_chunk", 0, 1000), ("engine.prefill", 390, 700),
             ("engine.queued", 790, 900)]
    r = tr.reduce(trace, lo, hi, spans)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s_per_device"] == pytest.approx([600e-9, 500e-9])
    assert r["busy_s"] == pytest.approx(550e-9)
    # Exposed: device 0 [350, 400) = 50, device 1 [300, 400) = 100.
    assert r["collective_exposed_s"] == pytest.approx(75e-9)
    assert r["program_s"] == {"decode_chunk": [400e-9], "prefill": [200e-9]}
    assert r["longest_gaps_s"] == pytest.approx([400e-9])
    # The innermost span over the gap [400, 800), not the widest.
    assert r["breakdown"]["idle_gaps"] == [["engine.prefill",
                                            pytest.approx(400e-9)]]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1 fusion"] == pytest.approx(300e-9)
    assert ops["while.1 while"] == pytest.approx(0.0)
    assert r["op_count"]["fusion.1 fusion"] == 1
    assert tr.reduce(trace, lo, hi)["breakdown"]["idle_gaps"][0][0] \
        == "unattributed"


def test_a_trace_without_device_work_is_refused():
    trace = _made_trace()
    with pytest.raises(ValueError, match="no operation ran"):
        tr.reduce(trace, 400, 800)
    with pytest.raises(ValueError, match="plane"):
        tr.reduce({"planes": trace["planes"][2:]}, 0, 1000)


def test_the_recorded_v5e_trace():
    """The events that began in 75 ms of `mistral7b.chat.flood` on the
    chip (PR 24): one ``prefill`` of the 1024 bucket and the decode
    steps around it, 32 slots, 16 layers."""
    with gzip.open(DATA / "v5e_flood_cut.json.gz", "rt") as f:
        trace = json.load(f)
    assert [p["name"] for p in tr.device_planes(trace)] == ["/device:TPU:0"]
    lo, hi = tr.window_of(trace, "no.such", "marks")
    assert 75e6 <= hi - lo < 120e6
    r = tr.reduce(trace, lo, hi)
    assert 0.5 < r["busy_s"] / r["window_s"] <= 1.0
    assert r["program_s"] == {"prefill": [pytest.approx(0.0558, rel=0.01)]}
    names = " ".join(n for n, _ in r["breakdown"]["device_ops"])
    assert "copy" in names and "fusion" in names
    kernel = [n for n in r["op_self_s"] if n.endswith("tpu_custom_call")]
    assert kernel == ["closed_call.16 custom-call bf16[1,8,4,128] "
                      "tpu_custom_call"]          # the unnamed decode kernel
    assert 0 < r["op_self_s"][kernel[0]] < 0.2 * r["busy_s"]
    assert r["op_count"][kernel[0]] > 100
    assert all(s >= 0 for s in r["op_self_s"].values())
    assert sum(r["op_self_s"].values()) <= r["busy_s"] * 1.001


def test_loader_reads_a_profile_taken_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.anchor"):
        pass
    with jax.profiler.TraceAnnotation("bench.span.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load_xplane(str(tmp_path))
    names = [e[0] for e in tr.host_events(trace, "bench.")]
    assert names.count("bench.anchor") == 1 and "bench.span.step" in names
    assert tr.device_planes(trace) == []          # no chip here
    stand_in = tr.cpu_stand_in(trace)
    assert len(tr.device_planes(stand_in)) == 1
    assert "PLANE /host:CPU" in tr.summary(trace)
