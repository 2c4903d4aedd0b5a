"""Chip counting from device nodes (core/accelerators.py,
core/resources.py): the node daemon must count chips WITHOUT loading the
TPU runtime — one process per host may own it."""

import glob

import pytest

from ray_tpu.core import accelerators, resources

# What the two kinds of host expose. VFIO hosts carry the container
# control node ``/dev/vfio/vfio`` beside the numbered groups: counting
# every entry reports one chip too many.
ACCEL_HOST = {"/dev/accel*": ["/dev/accel1", "/dev/accel0"]}
VFIO_HOST = {"/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1",
                                  "/dev/vfio/2", "/dev/vfio/3"],
             "/dev/vfio/*": ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2",
                             "/dev/vfio/3", "/dev/vfio/vfio"]}


@pytest.mark.parametrize("nodes,chips", [(ACCEL_HOST, 2), (VFIO_HOST, 4),
                                         ({}, 0)],
                         ids=["accel", "vfio", "none"])
def test_chip_count_from_device_nodes(monkeypatch, nodes, chips):
    monkeypatch.delenv("RTPU_TPU_CHIPS", raising=False)
    monkeypatch.setattr(glob, "glob", lambda pat: list(nodes.get(pat, [])))
    assert len(accelerators.local_chip_nodes()) == chips
    assert (accelerators.TPUAcceleratorManager
            .get_current_node_num_accelerators()) == chips
    n, labels = resources._detect_tpu()
    assert n == float(chips) and bool(labels) == bool(chips)


def test_env_override_wins_over_device_nodes(monkeypatch):
    monkeypatch.setenv("RTPU_TPU_CHIPS", "8")
    monkeypatch.setattr(glob, "glob", lambda pat: ["/dev/accel0"])
    assert resources._detect_tpu()[0] == 8.0
    assert (accelerators.TPUAcceleratorManager
            .get_current_node_num_accelerators()) == 8
