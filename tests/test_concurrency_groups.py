"""Actor concurrency groups + cluster-wide task events (reference test
model: python/ray/tests/test_concurrency_group.py and the GcsTaskManager
state-API tests)."""

import time

import pytest

import ray_tpu
from ray_tpu.util import state as state_api


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def test_concurrency_groups_isolate_saturation(cluster):
    """A saturated default group must not block methods in another group
    (reference: ConcurrencyGroupManager per-group executors)."""

    @ray_tpu.remote(num_cpus=0, concurrency_groups={"io": 2})
    class Worker:
        def __init__(self):
            self.events = []

        def slow_default(self):
            time.sleep(1.5)
            return "default-done"

        @ray_tpu.method(concurrency_group="io")
        def ping(self):
            return "pong"

    w = Worker.remote()
    assert ray_tpu.get(w.ping.remote(), timeout=30) == "pong"
    # Saturate the default group (max_concurrency=1) with a slow call...
    slow_ref = w.slow_default.remote()
    time.sleep(0.2)
    # ...the io group must still answer immediately.
    t0 = time.perf_counter()
    assert ray_tpu.get(w.ping.remote(), timeout=30) == "pong"
    io_latency = time.perf_counter() - t0
    assert io_latency < 1.0, f"io group blocked behind default: {io_latency}"
    assert ray_tpu.get(slow_ref, timeout=30) == "default-done"
    ray_tpu.kill(w)


def test_concurrency_group_parallelism_capped(cluster):
    """A group of size 2 runs at most 2 of its methods concurrently."""

    @ray_tpu.remote(num_cpus=0, concurrency_groups={"g": 2},
                    max_concurrency=4)
    class Capped:
        def __init__(self):
            import threading

            self._active = 0
            self._peak = 0
            self._lock = threading.Lock()

        @ray_tpu.method(concurrency_group="g")
        def work(self):
            with self._lock:
                self._active += 1
                self._peak = max(self._peak, self._active)
            time.sleep(0.3)
            with self._lock:
                self._active -= 1
            return True

        def peak(self):
            return self._peak

    c = Capped.remote()
    ray_tpu.get([c.work.remote() for _ in range(6)], timeout=60)
    peak = ray_tpu.get(c.peak.remote(), timeout=30)
    assert peak == 2, peak
    ray_tpu.kill(c)


def test_size_one_group_preserves_order(cluster):
    @ray_tpu.remote(num_cpus=0, concurrency_groups={"ordered": 1},
                    max_concurrency=8)
    class Ordered:
        def __init__(self):
            self.log = []

        @ray_tpu.method(concurrency_group="ordered")
        def step(self, i):
            self.log.append(i)
            return i

        def get_log(self):
            return list(self.log)

    o = Ordered.remote()
    ray_tpu.get([o.step.remote(i) for i in range(20)], timeout=60)
    assert ray_tpu.get(o.get_log.remote(), timeout=30) == list(range(20))
    ray_tpu.kill(o)


def test_list_tasks_sees_other_owners_tasks(cluster):
    """Tasks submitted INSIDE a worker (a different owner than this
    driver) must appear in the driver's list_tasks via the head's
    aggregated event ring (the VERDICT 'driver B sees driver A's tasks'
    criterion)."""

    @ray_tpu.remote
    def inner_task_xyz():
        return 1

    @ray_tpu.remote
    def submitter():
        # This worker OWNS these submissions; the driver does not.
        return sum(ray_tpu.get([inner_task_xyz.remote()
                                for _ in range(5)]))

    assert ray_tpu.get(submitter.remote(), timeout=60) == 5
    deadline = time.time() + 15
    seen = False
    while time.time() < deadline and not seen:
        tasks = state_api.list_tasks(limit=500)
        names = [t.get("name", "") for t in tasks
                 if t.get("state") == "FINISHED"]
        seen = any("inner_task_xyz" in n for n in names)
        if not seen:
            time.sleep(0.5)
    assert seen, "other owner's tasks never reached the head ring"
    # Owner attribution present on aggregated events.
    ev = [t for t in tasks if "inner_task_xyz" in t.get("name", "")][0]
    assert ev.get("owner"), ev
