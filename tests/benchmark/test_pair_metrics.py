"""`prefill_paired_pct` (PR 53) on hand-made ``counters`` snapshots: the
share where the counters are there, ``None`` on a run of a program
without them (the parent commit in the driver's comparison) and where
the window admitted nobody."""

import pytest

from benchmark.harness import manifest

M = manifest.load()
METRIC = "prefill_paired_pct"

START = {"prefill_pairs": 3, "prefix_hits": 2, "prefix_misses": 60}
END = {"prefill_pairs": 83, "prefix_hits": 6, "prefix_misses": 536}


def _run(start, end):
    return {"counters": {"start": start, "end": end}}


def _without(d, *keys):
    return {k: v for k, v in d.items() if k not in keys}


def test_the_reader_reads_two_admissions_a_pair_over_the_window():
    # 80 pairs among 4 + 476 admissions: a third of them went out paired.
    assert M.reader(METRIC)(_run(START, END)) == pytest.approx(
        100 * 2 * 80 / 480)
    none = dict(END, prefill_pairs=START["prefill_pairs"])
    assert M.reader(METRIC)(_run(START, none)) == 0.0


@pytest.mark.parametrize("gone", ["prefill_pairs", "prefix_hits",
                                  "prefix_misses"])
def test_the_reader_reads_nothing_on_a_program_without_its_counters(gone):
    read = M.reader(METRIC)
    assert read(_run(_without(START, gone), _without(END, gone))) is None
    assert read(_run(_without(START, gone), END)) is None   # not at the start
    assert read(_run(START, _without(END, gone))) is None
    assert read({}) is None                                 # a train run
    assert read(_run({}, {})) is None


def test_the_reader_reads_nothing_where_nobody_was_admitted():
    still = dict(END, prefix_hits=START["prefix_hits"],
                 prefix_misses=START["prefix_misses"])
    assert M.reader(METRIC)(_run(START, still)) is None


def test_the_manifest_lists_it_for_the_cell_that_pairs():
    entry = M.metrics[METRIC]
    assert entry["layer"] == "engine tick" and entry["better"] == "higher"
    assert entry["moves"] == "serve_tok_s"
    assert entry["source"] == "program_counter"
    assert entry["workloads"] == ["mistral7b.chat.flood"]
