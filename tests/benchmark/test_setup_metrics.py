"""PR 37's readers on hand-made runs: the six ``setup_*`` parts of
``setup_s`` from the program's compile account (``None`` where the
program keeps none: a parent commit), ``prefill_chunk_ms_per_ktok``
from two means; and the new names in a rehearsal of one serving cell
and of the training cell."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from benchmark.harness import manifest, setup_account
from ray_tpu.util import compile_cache

M = manifest.load()
SETUP = ("setup_trace_lower_s", "setup_cache_load_s", "setup_compile_s",
         "setup_init_s", "setup_rest_s")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def _stage(account, event, name, start, secs, hit=None):
    account._on_scalar(event, start, fun_name=name)
    if hit is not None:
        account._on_event("/jax/compilation_cache/compile_requests_use_cache")
        if hit:
            account._on_event("/jax/compilation_cache/cache_hits")
    account._on_duration(event, secs, fun_name=name)


@pytest.fixture
def account(monkeypatch, tmp_path):
    """A process whose set-up traced and lowered two programs (3 s),
    loaded one from the cache (0.5 s), compiled the other (7 s, twice
    requested) and some milliseconds in a constructor beside them."""
    acct = compile_cache.CompileCache(str(tmp_path))
    monkeypatch.setattr(compile_cache, "_ACCOUNT", acct)
    _stage(acct, TRACE, "prefill", 10.0, 1.0)
    _stage(acct, LOWER, "jit(prefill)", 11.0, 0.5)
    _stage(acct, BACKEND, "jit(prefill)", 11.5, 0.5, hit=True)
    _stage(acct, TRACE, "decode_chunk", 12.0, 1.0)
    _stage(acct, LOWER, "jit(decode_chunk)", 13.0, 0.5)
    _stage(acct, BACKEND, "jit(decode_chunk)", 14.0, 4.0, hit=False)
    _stage(acct, BACKEND, "jit(decode_chunk)", 18.0, 3.0)   # no cache asked
    with acct.phase("engine.init"):
        time.sleep(0.005)
    return acct


def test_the_parts_add_up_to_setup_s(account):
    run = {"setup_s": 20.0}
    got = {name: M.reader(name)(run) for name in SETUP}
    assert got["setup_trace_lower_s"] == pytest.approx(3.0)
    assert got["setup_cache_load_s"] == pytest.approx(0.5)
    assert got["setup_compile_s"] == pytest.approx(7.0)
    init_s = account.phases["engine.init"]["own_s"]
    assert got["setup_init_s"] == init_s >= 0.005
    assert got["setup_rest_s"] == pytest.approx(9.5 - init_s)
    assert got["setup_rest_s"] >= 0
    assert sum(got.values()) == pytest.approx(run["setup_s"])
    assert M.reader("setup_programs_requested")(run) == 3
    assert account.rows["decode_chunk"]["requests"] == 2


def test_nothing_to_read_without_the_account(monkeypatch):
    run = {"setup_s": 20.0}
    monkeypatch.setattr(compile_cache, "_ACCOUNT", None)
    for name in SETUP + ("setup_programs_requested",):
        assert M.reader(name)(run) is None
    # The parent commit's module has no `account` at all.
    monkeypatch.delattr(compile_cache, "account")
    assert setup_account.parts(run) is None
    assert M.reader("setup_rest_s")(run) is None


def test_every_new_metric_moves_setup_s_in_the_set_up_layer():
    for name in SETUP + ("setup_programs_requested",):
        entry = M.metrics[name]
        assert (entry["layer"], entry["moves"], entry["better"]) == \
            ("set-up", "setup_s", "lower")
        assert entry["source"] == "program_counter" and entry["workloads"]


@pytest.mark.parametrize("metric", ["prefill_chunk_ms_per_ktok",
                                    "prefill_chunk_ms_per_ktok.flood"])
def test_prefill_chunk_reader_divides_two_means(metric):
    read = M.reader(metric)
    assert read.__code__.co_filename.endswith("prefill_chunk_ms_per_ktok.py")
    start = {"prefill_chunks_dispatched": 10, "prefill_chunk_tokens": 1000,
             "prefill_tokens": 0}
    end = {"prefill_chunks_dispatched": 14, "prefill_chunk_tokens": 8000,
           "prefill_tokens": 24000}     # a whole prompt landed: not read
    run = {"trace": {"program_s": {"prefill": [0.10, 0.14, 0.12]}},
           "counters": {"start": {}, "end": {}, "trace_start": start,
                        "trace_end": end}}
    # 120 ms an execution over 1,750 real tokens a dispatched chunk.
    assert read(run) == pytest.approx(120.0 / 1750 * 1000)
    # The parent's program counts no chunk; an untraced run has no trace.
    old = {"prefill_tokens": 0}
    assert read(dict(run, counters={"trace_start": old, "trace_end": old})) \
        is None
    assert read(dict(run, trace=None)) is None
    assert read({"trace": run["trace"], "counters": {"start": {}, "end": {}}}) \
        is None
    assert read(dict(run, counters={"trace_start": start,
                                    "trace_end": start})) is None


@pytest.mark.parametrize("cell", ["mistral7b.chat.steady",
                                  "smollm2.sft.fsdp2tp2"])
def test_a_rehearsal_reads_the_set_up_metrics(cell, tmp_path):
    """On a copy of the benchmark's files: a traced run keeps its trace
    under its own root, and `test_rehearse.py` traces the same cell
    from the repository's at the same time."""
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    program = str(pathlib.Path(compile_cache.__file__).parents[2])
    env["PYTHONPATH"] = os.pathsep.join(
        [program] + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark/run.py"), "--workload",
         cell, "--seed", str(2**31 + 37), "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600, env=env,
        cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(SETUP + ("setup_programs_requested",)) \
        <= set(last["rehearsal_metric_names"])
