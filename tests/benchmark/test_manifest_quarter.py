"""At most a quarter of the cells, rounded down and at least one, may
ask for four chips: held at whatever number of cells the manifest has
(`test_manifest.py` holds it for a manifest of under eight;
`conftest.py` says why that is not enough on a copy with a further
cell)."""

import pytest

from benchmark.harness import manifest
from tests.benchmark.test_manifest import copy  # noqa: F401 — a fixture


@pytest.mark.parametrize("beyond", [0, 1])
def test_a_four_chip_cell_beyond_a_quarter_is_refused(copy, beyond):  # noqa: F811
    _, load = copy
    cells = manifest.load().data["workloads"]
    allowed = max(1, len(cells) // 4)

    def edit(data):
        four = [w for w in data["workloads"] if w["chips"] == 4]
        for w in data["workloads"]:
            if len(four) >= allowed + beyond:
                break
            if w["chips"] != 4:
                w["chips"] = 4
                four.append(w)

    if beyond:
        with pytest.raises(manifest.ManifestError, match="4-chip"):
            load(edit)
    else:
        assert sum(w["chips"] == 4 for w in load(edit).data["workloads"]
                   ) == allowed
