"""``BENCHMARK.json`` and the files it names.

The manifest lists metrics, configurations and cells; everything that
belongs to ONE of them is a file found by its name:

- ``<config.file>`` (under ``benchmark/configs/``): the sizes as run,
- ``benchmark/traffic/<traffic>.json``: one mix's parameters,
- ``benchmark/traffic_kinds/<kind>.py``: how a kind of mix becomes
  requests and is driven (named by the mix file's ``kind`` key),
- ``benchmark/metrics/<metric>.py``: one reader, ``read(run)`` (a
  metric ``<base>.<cells>`` falls back to ``<base>.py``),
- ``benchmark/drivers/<driver>.py``: how a kind of configuration comes
  up (named by the configuration file's ``driver`` key),
- ``benchmark/builders/<builder>.py``: what belongs to a model family —
  the program's configuration, the weights, the plain reference (named
  by the configuration file's optional ``builder`` key; absent, it is
  ``dense_llama``).

So a later PR adds a cell, a mix, a metric or a model family as new
files plus one entry here, and edits no file that exists. `load` checks the contract's
limits that can be checked without a run; the driver checks them again.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_METRIC_KEYS = {"end_to_end": {"name", "unit", "better", "bound", "source"},
                "per_layer": {"name", "unit", "better", "source", "layer",
                              "moves"}}


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


def _need(ok, what) -> None:
    if not ok:
        raise ManifestError(str(what))


def _dotted(obj, path: str):
    """``obj.a.b`` for ``"a.b"``; None where a step is missing."""
    for name in path.split("."):
        obj = getattr(obj, name, None)
    return obj


def _line(text, what) -> None:
    _need(isinstance(text, str) and 1 <= len(text) <= 200
          and "\n" not in text and "\t" not in text, f"{what}: {text!r}")


class Manifest:
    """The parsed manifest plus the lookups by name."""

    def __init__(self, data: dict, root: Path = ROOT):
        self.data = data
        self.root = Path(root)
        self.bench_dir = self.root / BENCH_DIR.name
        self.configs = {c["name"]: c for c in data["configs"]}
        self.cells = {w["name"]: w for w in data["workloads"]}
        self.metrics = {m["name"]: dict(m, kind=kind)
                        for kind in ("end_to_end", "per_layer")
                        for m in data[kind]}

    # ------------------------------------------------------------ lookups

    def cell(self, name: str) -> dict:
        _need(name in self.cells,
              f"no workload {name!r}; there are {sorted(self.cells)}")
        return self.cells[name]

    def config(self, cell: dict) -> dict:
        """The configuration file of a cell, as a dict."""
        with open(self.root / self.configs[cell["config"]]["file"]) as f:
            return json.load(f)

    def traffic(self, cell: dict) -> dict:
        with open(self.bench_dir / "traffic" / f"{cell['traffic']}.json") as f:
            return json.load(f)

    def metrics_of(self, cell_name: str, kind: str) -> list:
        """The metric entries of one kind that this cell reports."""
        return [m for m in self.data[kind]
                if cell_name in m.get("workloads", self.cells)]

    def reader(self, metric: str):
        """``read(run) -> number | None`` of one metric. A quantity split
        by cell because its cells report different end-to-end metrics
        (``decode_step_ms.flood``) shares the reader of its base name
        (``decode_step_ms.py``) unless it has a file of its own."""
        name = metric
        while not (self.bench_dir / "metrics" / f"{name}.py").is_file() \
                and "." in name:
            name = name.rsplit(".", 1)[0]
        return self._module("metrics", name).read

    def driver(self, name: str):
        return self._module("drivers", name)

    def builder(self, config: dict):
        """The builder of a configuration file (its dict): ``config``,
        ``init_params`` and ``reference`` of its model family. A driver
        lists what it calls of them as ``BUILDER_CALLS``; `check` looks
        for those."""
        return self._module("builders", config.get("builder", "dense_llama"))

    def kind(self, mix: dict):
        """The traffic kind of a mix file: how it becomes requests (or
        batches) and is driven."""
        return self._module("traffic_kinds", mix["kind"])

    def _module(self, folder: str, name: str):
        _need(NAME.match(name), f"bad name {name!r}")
        path = self.bench_dir / folder / f"{name}.py"
        _need(path.is_file(), f"{folder.rstrip('s')} {name!r} has no file {path}")
        # By path, not by import name: a metric's name may hold dots.
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{folder}.{name.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    # --------------------------------------------------------- validation

    def check(self) -> "Manifest":
        d = self.data
        _need(set(d) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"},
              f"top-level keys {sorted(d)}")
        _need(isinstance(d["run_seconds"], int)
              and 1 <= d["run_seconds"] <= 51, "run_seconds")
        _need(1 <= len(d["paths"]) <= 16 and 1 <= len(d["command"]) <= 32,
              "paths/command")
        for word in d["command"]:
            _line(word, "command")
        for p in d["paths"]:
            _need(re.match(r"[A-Za-z0-9_.\-/]{1,200}\Z", p)
                  and not p.startswith("/") and ".." not in p.split("/"),
                  f"path {p!r}")
        under = lambda f: any(f.startswith(p.rstrip("/") + "/")
                              for p in d["paths"])
        _need(1 <= len(d["configs"]) <= 24, "configs")
        _need(len(self.configs) == len(d["configs"]), "duplicate config name")
        files = set()
        for c in d["configs"]:
            _need(set(c) == {"name", "source", "file", "reduced", "why"},
                  f"config keys {sorted(c)}")
            _need(NAME.match(c["name"]), f"config name {c['name']!r}")
            _line(c["source"], "source"), _line(c["why"], "why")
            _need(under(c["file"]) and c["file"] not in files
                  and (self.root / c["file"]).is_file(),
                  f"config file {c['file']!r}")
            files.add(c["file"])
            _need(len(c["reduced"]) <= 16
                  and all(NAME.match(k) for k in c["reduced"]), "reduced")
        _need(1 <= len(d["workloads"]) <= 24, "workloads")
        _need(len(self.cells) == len(d["workloads"]), "duplicate cell name")
        pairs = set()
        for w in d["workloads"]:
            _need(set(w) == {"name", "config", "traffic", "chips", "why"},
                  f"workload keys {sorted(w)}")
            for k in ("name", "config", "traffic"):
                _need(NAME.match(w[k]), f"workload {k} {w[k]!r}")
            _line(w["why"], "why")
            _need(w["chips"] in (1, 4), f"chips {w['chips']}")
            _need(w["config"] in self.configs, f"config {w['config']!r}")
            _need((w["config"], w["traffic"]) not in pairs, "pair twice")
            pairs.add((w["config"], w["traffic"]))
            self.kind(self.traffic(w))
            config = self.config(w)
            driver, builder = self.driver(config["driver"]), self.builder(config)
            for path in getattr(driver, "BUILDER_CALLS", ()):
                _need(callable(_dotted(builder, path)),
                      f"builder of {w['config']!r} lacks {path}, which "
                      f"driver {config['driver']!r} calls")
        _need({w["config"] for w in d["workloads"]} == set(self.configs),
              "a configuration no cell uses")
        n4 = sum(w["chips"] == 4 for w in d["workloads"])
        _need(n4 <= max(1, len(d["workloads"]) // 4), "too many 4-chip cells")
        _need(len(self.metrics) == len(d["end_to_end"]) + len(d["per_layer"]),
              "duplicate metric name")
        _need(1 <= len(d["end_to_end"]) <= 16
              and 1 <= len(d["per_layer"]) <= 128, "metric counts")
        e2e = {m["name"] for m in d["end_to_end"]}
        _need("setup_s" in e2e, "no setup_s")
        for kind in ("end_to_end", "per_layer"):
            for m in d[kind]:
                _need(_METRIC_KEYS[kind] <= set(m)
                      <= _METRIC_KEYS[kind] | {"workloads"},
                      f"metric keys {sorted(m)}")
                _need(NAME.match(m["name"]), f"metric name {m['name']!r}")
                _need(UNIT.match(m["unit"]), f"unit {m['unit']!r}")
                _need(m["better"] in ("lower", "higher"), "better")
                _need(m["source"] in SOURCES, f"source {m['source']!r}")
                for cell in m.get("workloads", ()):
                    _need(cell in self.cells, f"metric cell {cell!r}")
                self.reader(m["name"])
        for m in d["end_to_end"]:
            _need(m["source"] in ("host_clock", "device_trace"), "e2e source")
            _need(0 < m["bound"] <= 0.1, f"bound of {m['name']}")
        for m in d["per_layer"]:
            _line(m["layer"], "layer")
            _need(m["moves"] in e2e, f"{m['name']} moves {m['moves']!r}")
            moved = self.metrics[m["moves"]]
            for cell in m.get("workloads", self.cells):
                _need(cell in moved.get("workloads", self.cells),
                      f"{m['name']} is in {cell} but {m['moves']} is not")
        for cell in self.cells:
            names = {m["name"] for m in self.metrics_of(cell, "end_to_end")}
            _need("setup_s" in names and len(names) >= 2
                  and self.metrics_of(cell, "per_layer"),
                  f"cell {cell} lacks setup_s, a second end-to-end metric "
                  f"or a per-layer metric")
        return self


def load(root: Path = ROOT) -> Manifest:
    """Parse and check ``<root>/BENCHMARK.json``."""
    path = Path(root) / "BENCHMARK.json"
    _need(path.stat().st_size <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    with open(path) as f:
        return Manifest(json.load(f), root).check()
