"""Out-of-order actor execution (reference:
src/ray/core_worker/transport/out_of_order_actor_submit_queue.h — calls
execute as they arrive; a delayed seq never gates its successors).
"""

import time

import pytest

import ray_tpu
from ray_tpu.core.config import GLOBAL_CONFIG as cfg


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_out_of_order_option_reaches_worker(cluster):
    """Plumbing: the option rides the actor spec to the hosting worker."""
    @ray_tpu.remote(allow_out_of_order_execution=True, max_concurrency=4)
    class OOActor:
        def probe(self):
            # The hosting worker's runtime can introspect its own actor.
            from ray_tpu.core.runtime_context import (
                current_worker_context, require_runtime)

            rt = require_runtime()
            aid = current_worker_context().get("actor_id")
            hosted = rt._hosted.get(aid)
            return bool(hosted and hosted.out_of_order)

    a = OOActor.remote()
    assert ray_tpu.get(a.probe.remote(), timeout=60) is True

    @ray_tpu.remote
    class Ordered:
        def probe(self):
            from ray_tpu.core.runtime_context import (
                current_worker_context, require_runtime)

            rt = require_runtime()
            aid = current_worker_context().get("actor_id")
            hosted = rt._hosted.get(aid)
            return bool(hosted and hosted.out_of_order)

    o = Ordered.remote()
    assert ray_tpu.get(o.probe.remote(), timeout=60) is False


def test_out_of_order_overlapping_execution(cluster):
    """With max_concurrency > 1, later calls may FINISH before earlier
    long-running ones — and results still land on the right refs."""
    @ray_tpu.remote(allow_out_of_order_execution=True, max_concurrency=4)
    class Sleeper:
        def work(self, i, delay):
            time.sleep(delay)
            return i

    s = Sleeper.remote()
    t0 = time.monotonic()
    slow = s.work.remote(0, 1.5)
    fast = [s.work.remote(i, 0.01) for i in range(1, 4)]
    # Fast calls complete while the slow one still runs.
    assert ray_tpu.get(fast, timeout=60) == [1, 2, 3]
    assert time.monotonic() - t0 < 1.4
    assert ray_tpu.get(slow, timeout=60) == 0


class TestOutOfOrderUnderChaos:
    @pytest.fixture()
    def chaos(self):
        cfg.set("rpc_chaos_failure_prob", 0.05)
        yield
        cfg.set("rpc_chaos_failure_prob", 0.0)

    def test_exactly_once_without_ordering(self, cluster, chaos):
        """Chaos-dropped pushes retry; dedup must keep execution
        exactly-once even though ordering is off (the seen-set dedup is
        the part the in-order buffer normally provides)."""
        @ray_tpu.remote(allow_out_of_order_execution=True)
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return True

            def get(self):
                return self.n

        c = Counter.remote()
        assert all(ray_tpu.get([c.inc.remote() for _ in range(80)],
                               timeout=180))
        assert ray_tpu.get(c.get.remote(), timeout=60) == 80
