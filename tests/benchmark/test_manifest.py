"""The manifest loader: finds every file by name, refuses what the
contract refuses, and takes a cell, a mix and a metric added purely as
new files plus one entry each."""

import json
import shutil

import pytest

from benchmark.harness import manifest


def test_the_repo_manifest_loads_and_every_file_is_found():
    m = manifest.load()
    assert list(m.cells) == ["mistral7b.chat.steady", "smollm2.sft.fsdp2tp2",
                             "mistral7b.chat.flood"]
    assert [m.cells[c]["chips"] for c in m.cells] == [1, 4, 1]
    for cell in m.cells.values():
        cfg = m.config(cell)
        assert cfg["source"] == m.configs[cell["config"]]["source"]
        assert set(cfg["reduced"]) == set(m.configs[cell["config"]]["reduced"])
        assert "assumed" in cfg and "deployment" in cfg
        assert hasattr(m.driver(cfg["driver"]), "run")
        kind = m.kind(m.traffic(cell))
        assert hasattr(kind, "batch") or (hasattr(kind, "requests")
                                          and hasattr(kind, "drive"))
    for name in m.metrics:
        assert callable(m.reader(name))
    # A metric split by cell shares its base name's reader.
    assert (m.reader("device_idle_pct.train").__code__.co_filename
            .endswith("device_idle_pct.py"))


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
