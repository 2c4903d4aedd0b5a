"""Lineage-based object recovery (SURVEY hard-part #3; reference test
model: python/ray/tests/test_reconstruction.py): kill the node holding a
task's large output; get() must transparently resubmit the creating task.
"""

import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=2)
    yield rt
    ray_tpu.shutdown()


def _affinity(node_id):
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    return NodeAffinitySchedulingStrategy(node_id=node_id, soft=True)


N = 200_000  # > inline threshold: results live in the node's plasma store


def test_lineage_store_eviction():
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.lineage import LineageRecord, LineageStore

    store = LineageStore(max_bytes=1500)
    oids = []
    for i in range(10):
        oid = ObjectID.from_random()
        oids.append(oid)
        store.record(bytes([i]) * 8, LineageRecord(
            b"x" * 400, ("k",), {}, None, f"t{i}", [oid], []))
    assert store.size_bytes() <= 1500
    assert store.evictions > 0
    # Newest records survive; oldest were evicted.
    assert store.for_object(oids[-1]) is not None
    assert store.for_object(oids[0]) is None


def test_lineage_eviction_keeps_recoverable_descendant():
    """Bytes-bounded FIFO evicts the OLDEST record even when a younger
    record's args point at its outputs: the descendant stays recoverable
    by its own spec (its resubmission re-fetches or best-effort-recovers
    the arg), and the evicted record's oid index entries are scrubbed —
    no dangling by_oid pointers at a dead record."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.lineage import LineageRecord, LineageStore

    # Record sizes: A = 400+64 = 464, B = 400+128 = 528, fill = 464.
    store = LineageStore(max_bytes=1000)
    oid_a = ObjectID.from_random()
    oid_b = ObjectID.from_random()
    store.record(b"task-a" + b"\0" * 2, LineageRecord(
        b"a" * 400, ("k",), {}, None, "produce", [oid_a], []))
    # B consumes A's output.
    store.record(b"task-b" + b"\0" * 2, LineageRecord(
        b"b" * 400, ("k",), {}, None, "double", [oid_b], [oid_a]))
    # Push exactly A (the FIFO head) out of the byte budget.
    oid_f = ObjectID.from_random()
    store.record(b"fill0--t", LineageRecord(
        b"f" * 400, ("k",), {}, None, "fill0", [oid_f], []))
    assert store.size_bytes() <= 1000
    assert store.evictions >= 1
    assert store.for_object(oid_a) is None  # ancestor evicted
    found = store.for_object(oid_b)  # descendant still recoverable
    assert found is not None and found[1].arg_ids == [oid_a]
    # The evicted record's index entries are gone, not dangling.
    assert oid_a not in store._by_oid


def test_lineage_rerecord_same_task_does_not_double_count():
    """The recovery path re-points a task's mapping at the resubmitted
    spec: re-recording one task id must replace, not leak bytes."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.lineage import LineageRecord, LineageStore

    store = LineageStore(max_bytes=1 << 20)
    oid = ObjectID.from_random()
    for _ in range(50):
        store.record(b"same-task", LineageRecord(
            b"x" * 300, ("k",), {}, None, "t", [oid], []))
    assert store.num_records() == 1
    assert store.size_bytes() == 300 + 64
    assert store.evictions == 0


def test_lineage_zero_budget_disables_cleanly():
    """max_lineage_bytes=0 turns lineage OFF: records are dropped at the
    door (no partial state, no index growth), lookups miss, and the
    single-record never-evict guard is irrelevant."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.lineage import LineageRecord, LineageStore

    store = LineageStore(max_bytes=0)
    oid = ObjectID.from_random()
    store.record(b"t1", LineageRecord(
        b"x" * 100, ("k",), {}, None, "t", [oid], []))
    assert store.for_object(oid) is None
    assert store.num_records() == 0
    assert store.size_bytes() == 0
    assert store.evictions == 0
    assert store._by_oid == {}


def test_lineage_single_oversized_record_survives():
    """One record larger than the whole budget is kept (the >1 guard):
    evicting the only record would make its own outputs unrecoverable
    for zero memory win."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.lineage import LineageRecord, LineageStore

    store = LineageStore(max_bytes=100)
    oid = ObjectID.from_random()
    store.record(b"big", LineageRecord(
        b"x" * 500, ("k",), {}, None, "t", [oid], []))
    assert store.for_object(oid) is not None
    assert store.num_records() == 1


def test_get_recovers_lost_object(cluster):
    node = cluster.add_node(num_cpus=2)
    time.sleep(1.5)

    @ray_tpu.remote(scheduling_strategy=_affinity(node.node_id))
    def produce(seed):
        return np.arange(seed, seed + N)

    ref = produce.remote(7)
    # Completion barrier WITHOUT pulling the bytes to the driver node
    # (fetch_local=False): the only copy stays on node B.
    ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=90,
                            fetch_local=False)
    assert ready

    cluster.kill_node(node)
    time.sleep(0.5)

    got = ray_tpu.get(ref, timeout=120)
    assert got[0] == 7 and got[-1] == 7 + N - 1


def test_transitive_recovery_chain(cluster):
    node = cluster.add_node(num_cpus=2)
    time.sleep(1.5)

    @ray_tpu.remote(scheduling_strategy=_affinity(node.node_id))
    def produce():
        return np.arange(N)

    @ray_tpu.remote(scheduling_strategy=_affinity(node.node_id))
    def double(x):
        return x * 2

    x_ref = produce.remote()
    y_ref = double.remote(x_ref)
    # Wait for completion WITHOUT pulling the values to the driver node
    # (fetch_local=False keeps the bytes only on node B).
    ready, _ = ray_tpu.wait([y_ref], num_returns=1, timeout=90,
                            fetch_local=False)
    assert ready

    cluster.kill_node(node)
    time.sleep(0.5)

    # y is lost; its recovery needs x, which is ALSO lost -> the owner
    # must resubmit produce() first, then double(x).
    got = ray_tpu.get(y_ref, timeout=120)
    assert got[0] == 0 and got[-1] == (N - 1) * 2
