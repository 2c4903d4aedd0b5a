"""The five readers of the engine's device queue (PR 52) on hand-made
``counters`` snapshots: the number where the counters are there, and
``None`` on a run of a program without them (the parent commit in the
driver's comparison) or where nothing happened in the window."""

import pytest

from benchmark.harness import manifest

M = manifest.load()
STEADY = ["mistral7b.chat.steady", "mistral7b.doc.steady"]

START = {"requests": 40, "prefill_wait_s": 4.0, "prefill_split": 38,
         "prefill_behind_s": 2.2, "prefill_own_s": 1.0,
         "chunk_period_s": 9.0, "chunk_steps_retired": 800,
         "chunk_own_s": 7.7, "chunk_steps_timed": 700,
         "device_dry_s": 0.5, "tick_loop_s": 20.0,
         "decode_steps": 10_000, "decode_steps_frozen": 300}
END = {"requests": 256, "prefill_wait_s": 23.44, "prefill_split": 238,
       "prefill_behind_s": 14.2, "prefill_own_s": 5.6,
       "chunk_period_s": 54.0, "chunk_steps_retired": 4_400,
       "chunk_own_s": 42.9, "chunk_steps_timed": 3_900,
       "device_dry_s": 0.95, "tick_loop_s": 65.0,
       "decode_steps": 110_000, "decode_steps_frozen": 3_300}
WANT = {
    "engine_prefill_behind_ms": 12.0 / 200 * 1e3,           # 60 a split
    "engine_prefill_own_ms": 4.6 / 200 * 1e3,               # 23
    "engine_tpot_stall_ms": (45.0 / 3_600 - 35.2 / 3_200) * 1e3,  # 12.5 - 11
    "engine_device_dry_pct": 0.45 / 45.0 * 100,             # 1 % of the loop
    "decode_frozen_step_pct": 3_000 / 100_000 * 100,        # 3 % of the steps
}
# What each reader divides by: where that stood still, nothing to read.
OVER = {"engine_prefill_behind_ms": "prefill_split",
        "engine_prefill_own_ms": "prefill_split",
        "engine_tpot_stall_ms": "chunk_steps_timed",
        "engine_device_dry_pct": "tick_loop_s",
        "decode_frozen_step_pct": "decode_steps"}
# ... and what it reads that the parent commit's program does not have.
NEW = {"engine_prefill_behind_ms": "prefill_behind_s",
       "engine_prefill_own_ms": "prefill_own_s",
       "engine_tpot_stall_ms": "chunk_period_s",
       "engine_device_dry_pct": "device_dry_s",
       "decode_frozen_step_pct": "decode_steps_frozen"}


def _run(start, end):
    return {"counters": {"start": start, "end": end}}


def _without(d, *keys):
    return {k: v for k, v in d.items() if k not in keys}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_reads_its_counters_over_the_window(metric):
    assert M.reader(metric)(_run(START, END)) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_reads_nothing_on_a_program_without_its_counters(metric):
    read = M.reader(metric)
    gone = NEW[metric]
    assert read(_run(_without(START, gone), _without(END, gone))) is None
    assert read(_run(_without(START, gone), END)) is None   # not at the start
    assert read({}) is None                                 # a train run
    assert read(_run({}, {})) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_reads_nothing_where_nothing_happened(metric):
    still = dict(END, **{OVER[metric]: START[OVER[metric]]})
    assert M.reader(metric)(_run(START, still)) is None


def test_the_split_adds_up_to_the_wait_it_splits():
    """Over the admissions the split covers, behind + own is
    ``engine_prefill_wait_ms`` less the fetch's last lines."""
    run = _run(START, END)
    parts = (M.reader("engine_prefill_behind_ms")(run)
             + M.reader("engine_prefill_own_ms")(run))
    whole = M.reader("engine_prefill_wait_ms")(run)
    assert parts == pytest.approx(83.0) and whole == pytest.approx(90.0)
    assert parts < whole


def test_the_stall_needs_both_a_cadence_and_a_timed_chunk():
    read = M.reader("engine_tpot_stall_ms")
    assert read(_run(_without(START, "chunk_own_s"),
                     _without(END, "chunk_own_s"))) is None
    untimed = dict(END, chunk_steps_retired=START["chunk_steps_retired"])
    assert read(_run(START, untimed)) is None


def test_the_five_sit_last_in_the_manifest_on_the_engine_tick_s_layer():
    names = [m["name"] for m in M.data["per_layer"]]
    new = ["engine_prefill_behind_ms", "engine_prefill_own_ms",
           "engine_tpot_stall_ms", "engine_device_dry_pct",
           "decode_frozen_step_pct"]
    at = names.index("kimi_held_pairs_pct") + 1
    assert names[at:at + len(new)] == new   # ... before what a later PR adds
    for name in new:
        entry = M.metrics[name]
        assert entry["layer"] == "engine tick"
        assert entry["source"] == "program_counter"
        assert entry["better"] == "lower"
        moved = M.metrics[entry["moves"]]
        assert set(entry["workloads"]) <= set(moved["workloads"])
    # (A later cell may join them: none is held to the cells of today.)
    assert "mistral7b.chat.flood" in \
        M.metrics["decode_frozen_step_pct"]["workloads"]
    for name in new[:4]:
        assert set(STEADY) <= set(M.metrics[name]["workloads"])


@pytest.mark.parametrize("cell", STEADY + ["mistral7b.chat.flood",
                                           "smollm2.sft.fsdp2tp2"])
def test_each_cell_reports_the_ones_its_entry_lists(cell):
    listed = {m["name"] for m in M.metrics_of(cell, "per_layer")} & set(WANT)
    want = (set(WANT) - {"decode_frozen_step_pct"} if cell in STEADY
            else {"decode_frozen_step_pct"} if cell.endswith(".flood")
            else set())
    assert listed == want
