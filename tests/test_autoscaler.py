"""Autoscaler tests: unmet demand triggers scale-up; idle nodes reap
(reference analog: python/ray/autoscaler/v2 tests + fake node provider).
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.autoscaler import Autoscaler, AutoscalerConfig, LocalNodeProvider


@pytest.fixture
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=2)
    yield rt
    ray_tpu.shutdown()


def test_infeasible_demand_triggers_scale_up_then_idle_reap(cluster):
    provider = LocalNodeProvider(cluster, node_types={"cpu": {"CPU": 4.0}})
    scaler = Autoscaler(cluster, provider, AutoscalerConfig(
        max_nodes=4, idle_timeout_s=3.0, demand_window_s=20.0))

    @ray_tpu.remote(num_cpus=4)
    def big():
        time.sleep(1.0)
        return ray_tpu.get_runtime_context().node_id

    # Infeasible on the 2-CPU head node: the lease layer records unmet
    # demand at the head while the task stays queued.
    refs = [big.remote() for _ in range(2)]

    # The demand report rides the lease/spillback path asynchronously: on
    # a loaded host one fixed sleep raced it (suite-order flake). Poll the
    # scale-up decision instead of betting on a single instant.
    deadline = time.monotonic() + 30
    launched = []
    while time.monotonic() < deadline and not launched:
        time.sleep(1.0)
        launched = scaler.step()["launched"]
    assert launched, "no scale-up despite infeasible demand"
    # The queued tasks complete on the new capacity.
    nids = ray_tpu.get(refs, timeout=120)
    assert len(provider.non_terminated_nodes()) >= 1
    new_nodes = set(provider.non_terminated_nodes())
    assert set(nids) <= new_nodes, "tasks did not run on autoscaled nodes"

    # Idle reap: no demand; after idle_timeout the nodes drain + die.
    # Each launched node's idle timer starts when IT is first seen idle
    # (the one that ran tasks goes idle later), so reaps can land in
    # different steps — poll until the provider is empty, not until the
    # first reap.
    deadline = time.monotonic() + 60
    reaped = []
    while time.monotonic() < deadline and provider.non_terminated_nodes():
        time.sleep(1.0)
        reaped += scaler.step()["reaped"]
    assert reaped, "idle autoscaled node was never reaped"
    assert not provider.non_terminated_nodes()


class _FakeHead:
    """Head stub: serves a canned get_demand state, records drains."""

    def __init__(self, state):
        self.state = state
        self.drained = []

    def retrying_call(self, method, *args, timeout=None):
        if method == "get_demand":
            return self.state
        if method == "drain_node":
            # Like the real head: a drained node leaves the node table,
            # so later get_demand calls no longer list it.
            self.drained.append(args[0])
            self.state["nodes"] = [n for n in self.state["nodes"]
                                   if n["node_id"] != args[0]]
            return None
        raise AssertionError(method)


class _FakeRT:
    def __init__(self, state):
        self.head = _FakeHead(state)


class _MockProvider:
    """Provider stub: tracks nodes in a set; terminate can be failed."""

    node_types = {"cpu": {"CPU": 4.0}}

    def __init__(self, nodes, fail_terminate=False):
        self.nodes = set(nodes)
        self.fail_terminate = fail_terminate
        self.terminated = []

    def create_node(self, node_type):
        raise AssertionError("no scale-up expected")

    def terminate_node(self, pid):
        if self.fail_terminate:
            raise RuntimeError("cloud API error")
        self.nodes.discard(pid)
        self.terminated.append(pid)

    def non_terminated_nodes(self):
        return sorted(self.nodes)


def _idle_state(node_ids):
    return {
        "unmet": [],
        "nodes": [{"node_id": nid, "alive": True,
                   "resources": {"CPU": 4.0}, "available": {"CPU": 4.0},
                   "labels": {}} for nid in node_ids],
    }


def test_reap_terminates_via_provider_deterministic():
    """A reported reap implies the provider no longer lists the node
    (VERDICT r4: reap must terminate through the provider, then report)."""
    state = _idle_state(["n1"])
    rt = _FakeRT(state)
    provider = _MockProvider(["n1"])
    scaler = Autoscaler(rt, provider, AutoscalerConfig(
        max_nodes=4, min_nodes=0, idle_timeout_s=0.0))
    scaler._managed["n1"] = None

    did = scaler.step()
    assert did["reaped"] == ["n1"]
    assert provider.non_terminated_nodes() == []
    assert rt.head.drained == ["n1"]
    # Every pid ever reported reaped is gone from the provider.
    assert not (set(did["reaped"])
                & set(provider.non_terminated_nodes()))


def test_reap_not_reported_when_provider_terminate_fails():
    state = _idle_state(["n1"])
    rt = _FakeRT(state)
    provider = _MockProvider(["n1"], fail_terminate=True)
    scaler = Autoscaler(rt, provider, AutoscalerConfig(
        max_nodes=4, min_nodes=0, idle_timeout_s=0.0))
    scaler._managed["n1"] = None

    did = scaler.step()
    assert did["reaped"] == []
    assert provider.non_terminated_nodes() == ["n1"]
    # Node stays managed, so the reap retries on a later pass.
    assert "n1" in scaler._managed
    provider.fail_terminate = False
    did = scaler.step()
    assert did["reaped"] == ["n1"]
    assert provider.non_terminated_nodes() == []


def test_scale_up_respects_max_nodes(cluster):
    provider = LocalNodeProvider(cluster, node_types={"cpu": {"CPU": 4.0}})
    scaler = Autoscaler(cluster, provider,
                        AutoscalerConfig(max_nodes=2, max_launch_per_step=8))

    @ray_tpu.remote(num_cpus=4)
    def big():
        time.sleep(0.2)
        return 1

    refs = [big.remote() for _ in range(12)]
    time.sleep(1.0)
    scaler.step()
    time.sleep(1.0)
    scaler.step()
    # head node + at most (max_nodes - 1) autoscaled (head counts toward
    # the cluster total the scaler clamps against).
    assert len(provider.non_terminated_nodes()) <= 2
    ray_tpu.get(refs, timeout=180)


def test_bin_packing_absorbs_multiple_demands_per_node(cluster):
    provider = LocalNodeProvider(cluster, node_types={"cpu": {"CPU": 4.0}})
    scaler = Autoscaler(cluster, provider, AutoscalerConfig(max_nodes=8))

    @ray_tpu.remote(num_cpus=2)
    def mid():
        time.sleep(1.5)
        return 1

    # Head has 2 CPUs: one mid runs there; the others queue. 4 unmet
    # 2-CPU demands fit in ONE 4-CPU node x2, not four nodes.
    refs = [mid.remote() for _ in range(5)]
    time.sleep(2.5)  # one backlog report cycle
    did = scaler.step()
    # 5 x 2-CPU demands pack into <= 3 x 4-CPU nodes (NOT one node per
    # demand); the exact count depends on how many had already dispatched
    # when the backlog snapshot was taken.
    assert 1 <= len(did["launched"]) <= 3, did
    ray_tpu.get(refs, timeout=120)


# ------------------------------------------------ the head's demand ring


def test_met_pick_takes_the_requesters_demand_out_of_the_ring():
    """A requester that starved and is then placed is no demand any more,
    and a lease block asked by the same owner for the same shape is the
    same demand, not one beside it. Until PR 31 both stood in the ring
    for the whole window: the autoscaler read two unmet TPU demands for
    one task that had already run, found its first slice busy with the
    idle lease, and bought a second
    (tests/test_autoscaler_gce.py::test_autoscaler_provisions_tpu_slice_end_to_end)."""
    from ray_tpu.cluster.head import HeadServer

    head = HeadServer()
    try:
        shape = {"TPU": 4.0, "CPU": 1.0}
        owner = "127.0.0.1:7001"
        key = (owner, tuple(sorted(shape.items())))
        for _ in range(3):  # one requester retrying is ONE demand
            assert head.rpc_pick_node(None, shape, None, None, key) is None
        # The owner's block request starves on the same identity.
        assert head._grant_block("b1", owner, shape, None, None, None) is None
        assert head.rpc_get_demand(None, 20.0)["unmet"] == [shape]
        # Another owner of the same shape is another demand.
        other = ("127.0.0.1:7002", key[1])
        assert head.rpc_pick_node(None, shape, None, None, other) is None
        assert head.rpc_get_demand(None, 20.0)["unmet"] == [shape, shape]

        head.rpc_register_node(None, "n1", "127.0.0.1:1",
                               {"TPU": 4.0, "CPU": 8.0}, {}, "store")
        assert head.rpc_pick_node(None, shape, None, None, key) is not None
        # Met: the first owner's entries are gone, the other's stand.
        assert head.rpc_get_demand(None, 20.0)["unmet"] == [shape]
        assert head._unmet_keys == {other}
        assert head.rpc_pick_node(None, shape, None, None, other) is not None
        assert head.rpc_get_demand(None, 20.0)["unmet"] == []
        assert not head._unmet_keys and not head._unmet_demand
    finally:
        head.shutdown()
