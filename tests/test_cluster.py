"""Cluster-mode integration tests: multi-process runtime over the framed RPC
plane and the native shm object store.

Parity model: python/ray/tests/test_basic*.py / test_actor*.py /
test_placement_group*.py running against an in-process fake multi-node
cluster (reference: python/ray/cluster_utils.py:135) — here real head/node/
worker subprocesses on one machine.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import ActorDiedError, TaskError


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, object_store_memory=256 << 20)
    yield rt
    ray_tpu.shutdown()


def test_put_get_small_and_large(cluster):
    assert ray_tpu.get(ray_tpu.put({"a": 1})) == {"a": 1}
    big = np.arange(1_000_000)
    assert np.array_equal(ray_tpu.get(ray_tpu.put(big)), big)


def test_task_roundtrip_and_parallelism(cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2), timeout=60) == 3
    refs = [add.remote(i, i) for i in range(40)]
    assert ray_tpu.get(refs, timeout=60) == [2 * i for i in range(40)]


def test_long_tasks_run_concurrently(cluster):
    """N sleeping tasks on an N-CPU cluster overlap instead of
    pipelining onto one worker (the per-worker pipeline hides RTT for
    short tasks; it must not serialize long ones)."""

    @ray_tpu.remote
    def nap():
        time.sleep(1.0)
        return 1

    assert sum(ray_tpu.get([nap.remote() for _ in range(4)],
                           timeout=60)) == 4  # warm the pool
    t0 = time.monotonic()
    assert sum(ray_tpu.get([nap.remote() for _ in range(4)],
                           timeout=60)) == 4
    elapsed = time.monotonic() - t0
    assert elapsed < 3.0, f"sleep tasks serialized ({elapsed:.1f}s)"


def test_force_cancel_kills_running_task(cluster):
    """ray_tpu.cancel(force=True) interrupts user code mid-flight
    (reference: ray.cancel force_kill) and frees the worker's CPU."""
    from ray_tpu.exceptions import TaskCancelledError, WorkerCrashedError

    @ray_tpu.remote
    def stuck():
        time.sleep(300)
        return "never"

    ref = stuck.remote()
    time.sleep(1.0)  # let it reach user code
    ray_tpu.cancel(ref, force=True)
    with pytest.raises((TaskCancelledError, TaskError,
                        WorkerCrashedError)):
        ray_tpu.get(ref, timeout=30)

    # The CPU the stuck task held is free again: fresh work completes.
    @ray_tpu.remote
    def ok():
        return 42

    assert ray_tpu.get([ok.remote() for _ in range(4)],
                       timeout=60) == [42] * 4


def test_nested_tasks(cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(add.remote(x, 10), timeout=30)

    assert ray_tpu.get(outer.remote(5), timeout=60) == 15


def test_large_return_through_store(cluster):
    @ray_tpu.remote
    def make():
        return np.ones(500_000)

    assert ray_tpu.get(make.remote(), timeout=60).sum() == 500_000


def test_ref_args_cross_worker(cluster):
    @ray_tpu.remote
    def make():
        return np.arange(200_000)

    @ray_tpu.remote
    def consume(x):
        return int(x.sum())

    ref = make.remote()
    assert ray_tpu.get(consume.remote(ref), timeout=60) == sum(range(200_000))


def test_error_propagation(cluster):
    @ray_tpu.remote
    def boom():
        raise ValueError("bang")

    with pytest.raises(TaskError) as ei:
        ray_tpu.get(boom.remote(), timeout=60)
    assert "ValueError" in str(ei.value)


def test_actor_lifecycle(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def inc(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote(10)
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 11
    assert ray_tpu.get(c.inc.remote(5), timeout=30) == 16
    ray_tpu.kill(c)
    with pytest.raises(ActorDiedError):
        ray_tpu.get(c.inc.remote(), timeout=30)


def test_named_actor(cluster):
    @ray_tpu.remote
    class Svc:
        def ping(self):
            return "pong"

    Svc.options(name="svc_cluster_test").remote()
    h = ray_tpu.get_actor("svc_cluster_test")
    assert ray_tpu.get(h.ping.remote(), timeout=60) == "pong"


def test_wait(cluster):
    @ray_tpu.remote
    def quick():
        return 1

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return 2

    q, s = quick.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([q, s], num_returns=1, timeout=30)
    assert ready and ready[0] == q
    assert not_ready == [s]


def test_actor_restart_semantics(cluster):
    @ray_tpu.remote(max_restarts=1)
    class Fragile:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def die(self):
            import os

            os._exit(1)

    f = Fragile.remote()
    assert ray_tpu.get(f.inc.remote(), timeout=60) == 1
    with pytest.raises(Exception):
        ray_tpu.get(f.die.remote(), timeout=15)
    # Poll until the restarted incarnation answers (state is reset).
    deadline = time.monotonic() + 60
    while True:
        try:
            v = ray_tpu.get(f.inc.remote(), timeout=15)
            break
        except ActorDiedError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
    assert v == 1


def test_worker_crash_task_retry(cluster):
    """A task whose worker dies mid-run is retried on a fresh worker
    (system failures retry by default, reference task_manager semantics)."""

    @ray_tpu.remote
    def flaky(marker_path):
        import os

        if not os.path.exists(marker_path):
            open(marker_path, "w").close()
            os._exit(1)  # simulate worker crash on first attempt
        return "survived"

    marker = f"/tmp/rtpu_flaky_{time.time()}"
    assert ray_tpu.get(flaky.remote(marker), timeout=90) == "survived"


class TestMultiNode:
    @pytest.fixture(scope="class")
    def two_nodes(self, cluster):
        node = cluster.add_node(num_cpus=4, resources={"ACCEL_FAKE": 2.0})
        time.sleep(1.5)  # registration + heartbeat
        yield cluster, node

    def test_cluster_resources_aggregate(self, two_nodes):
        total = ray_tpu.cluster_resources()
        assert total.get("CPU", 0) >= 8.0
        assert total.get("ACCEL_FAKE") == 2.0

    def test_custom_resource_placement(self, two_nodes):
        cluster, node = two_nodes

        @ray_tpu.remote(resources={"ACCEL_FAKE": 1.0})
        def where():
            return ray_tpu.get_runtime_context().node_id

        assert ray_tpu.get(where.remote(), timeout=60) == node.node_id

    def test_cross_node_object_transfer(self, two_nodes):
        @ray_tpu.remote(resources={"ACCEL_FAKE": 1.0})
        def produce():
            return np.arange(300_000)

        @ray_tpu.remote
        def reduce_(x):
            return int(x.sum())

        got = ray_tpu.get(reduce_.remote(produce.remote()), timeout=90)
        assert got == sum(range(300_000))

    def test_spread_strategy(self, two_nodes):
        @ray_tpu.remote(scheduling_strategy="SPREAD")
        def where():
            return ray_tpu.get_runtime_context().node_id

        # Sequential submissions: the head's round-robin must alternate
        # nodes whenever both are feasible.
        nids = set()
        for _ in range(6):
            nids.add(ray_tpu.get(where.remote(), timeout=90))
        assert len(nids) == 2

    def test_placement_group_strict_spread(self, two_nodes):
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy)

        pg = placement_group([{"CPU": 1}, {"CPU": 1}],
                             strategy="STRICT_SPREAD")
        assert pg.ready(timeout=30)

        @ray_tpu.remote(scheduling_strategy=PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=0))
        def inside():
            return ray_tpu.get_runtime_context().node_id

        assert ray_tpu.get(inside.remote(), timeout=60)
        remove_placement_group(pg)


def test_node_label_scheduling_strategy(cluster):
    """Hard label match routes to the labeled node; SliceAffinity sugar
    rides the same path (reference: NodeLabelSchedulingStrategy,
    scheduling_strategies.py:135)."""
    import time as _time

    from ray_tpu.core.task_spec import (NodeLabelSchedulingStrategy,
                                        SliceAffinitySchedulingStrategy)

    rt = cluster
    labeled = rt.add_node(num_cpus=2, labels={"zone": "z9",
                                              "tpu-slice": "slice-a"})
    deadline = _time.time() + 30
    while _time.time() < deadline:
        if any(n["node_id"] == labeled.node_id and n["alive"]
               for n in rt.nodes()):
            break
        _time.sleep(0.25)

    @ray_tpu.remote(num_cpus=1)
    def where():
        return ray_tpu.get_runtime_context().node_id

    got = ray_tpu.get(where.options(
        scheduling_strategy=NodeLabelSchedulingStrategy(
            hard=(("zone", "z9"),))).remote(), timeout=60)
    assert got == labeled.node_id
    got = ray_tpu.get(where.options(
        scheduling_strategy=SliceAffinitySchedulingStrategy(
            slice_name="slice-a")).remote(), timeout=60)
    assert got == labeled.node_id
    # Unsatisfiable hard label: infeasible — the SPECIFIC scheduling
    # failure, not any error (a translation bug must fail this test).
    import pytest as _pytest

    with _pytest.raises(Exception, match="no feasible|timed out|Timeout"):
        ray_tpu.get(where.options(
            scheduling_strategy=NodeLabelSchedulingStrategy(
                hard=(("zone", "nowhere"),))).remote(), timeout=15)
