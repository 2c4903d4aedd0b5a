"""`test_manifest.py`'s copy-with-a-further-cell cases against the
manifest's OWN number of cells. That file (PR 28) asserts ``== 4`` on
the copy, which holds only while the manifest has three cells; a PR
that adds a cell may edit no file the benchmark has, so the same two
cases run here with the count read from the manifest. The next
`benchmark` PR corrects the 4 there and drops this file; until then
those two cases fail at that line and these two guard what they
guarded. (The names keep ``suite_passes_on_a_copy``: the run on the
copy deselects them. One case a file, `test_manifest_open_family.py`
has the other: each takes minutes, and the tier-1 run deals whole files
to its workers.)"""

import os
import shutil
import subprocess
import sys

from benchmark.harness import manifest
from tests.benchmark.test_manifest import (  # noqa: F401 — `copy` is a fixture
    FIRST_CELLS, _a_family_of_its_own, _a_fourth_workload, copy)


def the_suite_passes_on_a_copy(copy, add, cell):
    root, load = copy
    m = load(lambda data: add(root / "benchmark", data))
    assert list(m.cells)[-1] == cell
    assert len(m.cells) == len(manifest.load().cells) + 1
    assert set(FIRST_CELLS) < set(m.cells)
    shutil.copytree(manifest.ROOT / "tests/benchmark", root / "tests/benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "tests/__init__.py").write_text("")
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(PYTHONPATH=f"{root}{os.pathsep}{manifest.ROOT}",
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    # The same command as there: every test of ``tests/benchmark/``.
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


def test_the_suite_passes_on_a_copy_with_a_further_workload(copy):  # noqa: F811
    the_suite_passes_on_a_copy(copy, _a_fourth_workload, "mistral7b.chat.light")
