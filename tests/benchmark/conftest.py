"""One case of `test_manifest.py` holds only while the manifest has
under eight cells: ``(["workloads", 2, "chips"], 4)``, "a second 4-chip
cell", is refused because a quarter of the cells, rounded down and at
least one, may ask for four chips; from eight cells on two may, and the
edit is no fault. The manifest has seven cells since PR 40, so the
suite run on a copy WITH A FURTHER CELL (`test_manifest_open*.py`) has
eight. A PR that adds a cell may edit no file the benchmark has, so the
case is skipped where it cannot hold, and `test_manifest_quarter.py`
holds the rule at whatever count the manifest has. The next `benchmark`
PR computes the case in `test_manifest.py` and drops this file."""

import pytest

from benchmark.harness import manifest

_CASE = "test_manifest.py::test_what_the_contract_refuses[path8-4]"


def pytest_collection_modifyitems(config, items):
    if len(manifest.load().cells) // 4 < 2:
        return
    for item in items:
        if item.nodeid.endswith(_CASE):
            item.add_marker(pytest.mark.skip(
                reason="two 4-chip cells are allowed from eight cells on"))
