"""`mla_rows_streamed_pct` (PR 58): on a rehearsal's run of a cell that
calls the latent decode-attention kernel (the program's own counters
over a window), on hand-made snapshots, and ``None`` on a run of a
program without the counter (the parent commit in the driver's
comparison)."""

import time

import pytest

from benchmark.harness import context, manifest

M = manifest.load()
METRIC = "mla_rows_streamed_pct"
CELL = "glm47flash.code.flood"

START = {"mla_decode_rows": 1000, "mla_decode_rows_streamed": 1280}
END = {"mla_decode_rows": 41000, "mla_decode_rows_streamed": 47360}


def _run(start, end):
    return {"counters": {"start": start, "end": end}}


def _without(d, *keys):
    return {k: v for k, v in d.items() if k not in keys}


def test_the_reader_reads_the_rows_fetched_over_the_rows_asked_for():
    # 46,080 rows in whole blocks for 40,000 asked for.
    assert M.reader(METRIC)(_run(START, END)) == pytest.approx(115.2)
    exact = dict(END, mla_decode_rows_streamed=41280)
    assert M.reader(METRIC)(_run(START, exact)) == pytest.approx(100.0)


@pytest.mark.parametrize("gone", ["mla_decode_rows",
                                  "mla_decode_rows_streamed"])
def test_the_reader_reads_nothing_on_a_program_without_its_counters(gone):
    read = M.reader(METRIC)
    assert read(_run(_without(START, gone), _without(END, gone))) is None
    assert read(_run(_without(START, gone), END)) is None   # not at the start
    assert read(_run(START, _without(END, gone))) is None
    assert read({}) is None                                 # a train run
    assert read(_run({}, {})) is None


def test_the_reader_reads_nothing_where_no_step_ran():
    assert M.reader(METRIC)(_run(START, START)) is None


def test_a_rehearsal_streams_whole_blocks_for_the_rows_it_asks_for():
    """The cell's driver at the rehearsal's sizes, in this process: the
    engine's own counters over the window. The parent's program, which
    lacks the counter, reads nothing from the same run."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=2**31 + 41,
                              seconds=1.0, t_start=time.perf_counter(),
                              rehearse=True)
    run = m.driver(ctx.config["driver"]).run(ctx)
    assert run["correct"], run.get("why_incorrect")
    end = run["counters"]["end"]
    assert end["mla_decode_rows_streamed"] >= end["mla_decode_rows"] > 0
    assert m.reader(METRIC)(run) >= 100.0
    parent = {k: _without(v, "mla_decode_rows_streamed")
              for k, v in run["counters"].items()}
    assert m.reader(METRIC)(dict(run, counters=parent)) is None


def test_the_manifest_lists_it_for_the_cells_whose_step_counts_it():
    entry = M.metrics[METRIC]
    assert entry["layer"] == "kernels" and entry["better"] == "lower"
    assert entry["moves"] == "serve_tok_s" and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert entry["workloads"] == ["glm47flash.code.flood",
                                  "kimilinear.reason.flood",
                                  "xing4.rag.flood"]
