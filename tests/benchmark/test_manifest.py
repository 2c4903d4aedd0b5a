"""The manifest loader: finds every file by name, refuses what the
contract refuses, and takes a cell, a mix and a metric added purely as
new files plus one entry each."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import manifest


# The first three cells; what comes after them is any later PR's to add.
FIRST_CELLS = {"mistral7b.chat.steady": 1, "smollm2.sft.fsdp2tp2": 4,
               "mistral7b.chat.flood": 1}


def test_the_repo_manifest_loads_and_every_file_is_found():
    # `load` holds EVERY cell, the later ones too, to `check`: names,
    # pairs, at most 24 cells, a quarter of them on four chips.
    m = manifest.load()
    for name, chips in FIRST_CELLS.items():
        assert m.cell(name)["chips"] == chips
    for cell in m.cells.values():
        cfg = m.config(cell)
        assert cfg["source"] == m.configs[cell["config"]]["source"]
        assert set(cfg["reduced"]) == set(m.configs[cell["config"]]["reduced"])
        assert "assumed" in cfg and "deployment" in cfg
        driver = m.driver(cfg["driver"])
        assert hasattr(driver, "run")
        for call in driver.BUILDER_CALLS:       # what `check` looked for
            assert callable(manifest._dotted(m.builder(cfg), call))
        kind = m.kind(m.traffic(cell))
        assert hasattr(kind, "batch") or (hasattr(kind, "requests")
                                          and hasattr(kind, "drive"))
    for name in m.metrics:
        assert callable(m.reader(name))
    # A metric split by cell shares its base name's reader.
    assert (m.reader("device_idle_pct.train").__code__.co_filename
            .endswith("device_idle_pct.py"))
    # A configuration file that names no builder is a dense Llama.
    assert m.builder({}).__name__.endswith("dense_llama")


@pytest.fixture
def copy(tmp_path):
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = json.loads((manifest.ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "benchmark/traffic/kindless.json").write_text(
        json.dumps({"kind": "no_such_kind"}))

    def load(edit):
        edit(data)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
        return manifest.load(tmp_path)

    return tmp_path, load


def test_a_cell_a_mix_and_a_metric_come_in_as_new_files(copy):
    root, load = copy
    bench = root / "benchmark"
    base = json.loads((bench / "configs/smollm2-1.7b.json").read_text())
    (bench / "configs/smollm2-served.json").write_text(json.dumps(
        dict(base, driver="serve_local")))
    steady = json.loads((bench / "traffic/chat.steady.json").read_text())
    # ... of a KIND that is a new file too: arrivals in bursts of four.
    (bench / "traffic/chat.burst.json").write_text(json.dumps(
        dict(steady, kind="open_bursts", rate_rps=2.0)))
    (bench / "traffic_kinds/open_bursts.py").write_text(
        "import importlib.util, pathlib\n"
        "from benchmark.harness.traffic import Request\n"
        "_spec = importlib.util.spec_from_file_location('open_dealt',\n"
        "    pathlib.Path(__file__).with_name('open_dealt.py'))\n"
        "_dealt = importlib.util.module_from_spec(_spec)\n"
        "_spec.loader.exec_module(_dealt)\n"
        "drive = _dealt.drive\n"
        "def requests(mix, seed, seconds, vocab, max_total):\n"
        "    even = _dealt.requests(mix, seed, seconds, vocab, max_total)\n"
        "    return [Request(even[i - i % 4].due_s, r.prompt_ids,\n"
        "                    r.answer_len) for i, r in enumerate(even)]\n")
    (bench / "metrics/slo_share_pct.py").write_text(
        "def read(run):\n    return 100.0 * run['ok'] / run['n']\n")

    def edit(d):
        d["configs"].append({"name": "smollm2-served", "source": "x",
                             "file": "benchmark/configs/smollm2-served.json",
                             "reduced": [], "why": "w"})
        d["workloads"].append(
            {"name": "smollm2.chat.burst", "config": "smollm2-served",
             "traffic": "chat.burst", "chips": 1, "why": "w"})
        for m in d["end_to_end"]:
            if m["name"] == "ttft_p50_ms":
                m["workloads"].append("smollm2.chat.burst")
        d["per_layer"].append(
            {"name": "slo_share_pct", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "serve front",
             "moves": "ttft_p50_ms", "workloads": ["smollm2.chat.burst"]})

    m = load(edit)
    cell = m.cell("smollm2.chat.burst")
    mix = m.traffic(cell)
    assert mix["rate_rps"] == 2.0
    burst = m.kind(mix).requests(mix, 5, 10.0, 1000, 1016)
    assert len({r.due_s for r in burst}) == -(-len(burst) // 4)
    assert callable(m.kind(mix).drive)
    assert m.config(cell)["driver"] == "serve_local"
    assert m.reader("slo_share_pct")({"ok": 1, "n": 4}) == 25.0
    assert [x["name"] for x in m.metrics_of("smollm2.chat.burst",
                                            "per_layer")] == ["slo_share_pct"]


def _reports_like(data, cell, like):
    """``cell`` joins every metric that lists ``like``: entries, no edit
    to a file."""
    for m in data["end_to_end"] + data["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)


def _a_fourth_workload(bench, data):
    """A further cell of a configuration that is there: a new mix file
    and one entry."""
    steady = json.loads((bench / "traffic/chat.steady.json").read_text())
    (bench / "traffic/chat.light.json").write_text(json.dumps(
        dict(steady, rate_rps=2.0)))
    data["workloads"].append(
        {"name": "mistral7b.chat.light", "config": "mistral-7b-v0.3-l16",
         "traffic": "chat.light", "chips": 1, "why": "w"})
    _reports_like(data, "mistral7b.chat.light", "mistral7b.chat.steady")


def _a_family_of_its_own(bench, data):
    """A configuration whose builder and plain reference are new files
    (stubs over the dense ones; the reference leaves a mark)."""
    base = json.loads((bench / "configs/mistral-7b-v0.3-l16.json").read_text())
    (bench / "configs/stub-family.json").write_text(json.dumps(
        dict(base, name="stub-family", builder="stub_family")))
    (bench / "builders/stub_family.py").write_text(
        # By path: in this test's own process ``benchmark`` is the repo's.
        "import importlib.util, pathlib\n"
        "from benchmark.builders.dense_llama import config, init_params\n"
        "_spec = importlib.util.spec_from_file_location('stub_reference',\n"
        "    pathlib.Path(__file__).parents[1] / 'reference/stub_reference.py')\n"
        "reference = importlib.util.module_from_spec(_spec)\n"
        "_spec.loader.exec_module(reference)\n")
    (bench / "reference/stub_reference.py").write_text(
        "import pathlib\n"
        "from benchmark.reference import dense_decoder\n"
        "def logits_at(params, tokens, rows, c):\n"
        "    pathlib.Path(__file__).with_suffix('.used').write_text('x')\n"
        "    return dense_decoder.logits_at(params, tokens, rows, c)\n")
    data["configs"].append(
        {"name": "stub-family", "source": base["source"],
         "file": "benchmark/configs/stub-family.json",
         "reduced": list(base["reduced"]), "why": "w"})
    data["workloads"].append(
        {"name": "stub.chat.steady", "config": "stub-family",
         "traffic": "chat.steady", "chips": 1, "why": "w"})
    _reports_like(data, "stub.chat.steady", "mistral7b.chat.steady")


@pytest.mark.parametrize("add, cell", [
    (_a_fourth_workload, "mistral7b.chat.light"),
    (_a_family_of_its_own, "stub.chat.steady")])
def test_the_suite_passes_on_a_copy_with_a_further_cell(copy, add, cell):
    """What a later PR does: new files and manifest entries, no edit.
    The copy's manifest loads, and every test of ``tests/benchmark/``
    passes THERE (they read the manifest beside the ``benchmark/`` they
    import): none enumerates the cells it expects, and the new cell
    rehearses with the others, traced and not."""
    root, load = copy
    m = load(lambda data: add(root / "benchmark", data))
    assert list(m.cells)[-1] == cell and len(m.cells) == 4
    assert set(FIRST_CELLS) < set(m.cells)
    shutil.copytree(manifest.ROOT / "tests/benchmark", root / "tests/benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "tests/__init__.py").write_text("")
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(PYTHONPATH=f"{root}{os.pathsep}{manifest.ROOT}",
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-x", "-rA",
         "-p", "no:cacheprovider", "-k", "not suite_passes_on_a_copy"],
        cwd=root, env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    for trace in (0, 1):
        assert (f"PASSED tests/benchmark/test_rehearse.py::"
                f"test_rehearsal_runs_every_cell[{cell}-{trace}]") in out.stdout
    used = root / "benchmark/reference/stub_reference.used"
    assert used.exists() == (add is _a_family_of_its_own)


@pytest.mark.parametrize("config, ok", [("smollm2-1.7b", True),
                                        ("mistral-7b-v0.3-l16", False)])
def test_a_builder_is_held_to_what_its_driver_calls(copy, config, ok):
    """A family that only trains gives a reference with ``loss`` and no
    ``logits_at``: the training driver takes it, the serving driver's
    configuration is refused at `load`, not on the chip."""
    root, load = copy
    bench = root / "benchmark"
    (bench / "builders/train_only.py").write_text(
        "import types\n"
        "from benchmark.builders.dense_llama import config\n"
        "reference = types.SimpleNamespace(loss=lambda params, tokens, c: 0.0)\n")
    path = bench / f"configs/{config}.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    builder="train_only")))
    if ok:
        m = load(lambda d: None)
        assert m.builder(m.config(m.cell("smollm2.sft.fsdp2tp2"))
                         ).__name__.endswith("train_only")
    else:
        with pytest.raises(manifest.ManifestError, match="init_params"):
            load(lambda d: None)


def _set(path, value):
    def edit(d):
        node = d
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return edit


@pytest.mark.parametrize("path, value", [
    (["workloads", 0, "name"], "has space"),
    (["workloads", 0, "name"], "a/b"),
    (["workloads", 0, "name"], "x" * 65),
    (["workloads", 0, "traffic"], "no.such.mix"),
    (["workloads", 0, "traffic"], "kindless"),      # a mix of no known kind
    (["workloads", 0, "chips"], 2),
    (["workloads", 0, "why"], "two\nlines"),
    (["workloads", 0, "extra"], 1),
    (["workloads", 2, "chips"], 4),                 # a second 4-chip cell of 3
    (["workloads", 2, "traffic"], "chat.steady"),   # the pair twice
    (["configs", 0, "name"], "-leading"),
    (["configs", 1, "file"], "ray_tpu/models/llama.py"),
    (["end_to_end", 0, "unit"], "tokens per second"),
    (["end_to_end", 0, "unit"], "µs"),
    (["end_to_end", 0, "bound"], 0.2),
    (["end_to_end", 0, "better"], "smaller"),
    (["end_to_end", 0, "source"], "program_counter"),
    (["end_to_end", 4, "name"], "set_up_s"),        # no setup_s left
    (["per_layer", 0, "moves"], "serve_tok_s"),     # not reported in that cell
    (["per_layer", 0, "name"], "no_reader_for_this"),
    (["per_layer", 0, "why"], "metrics carry no why"),
    (["run_seconds"], 52),
    (["paths", 0], "../benchmark"),
])
def test_what_the_contract_refuses(copy, path, value):
    _, load = copy
    with pytest.raises((manifest.ManifestError, FileNotFoundError)):
        load(_set(path, value))
