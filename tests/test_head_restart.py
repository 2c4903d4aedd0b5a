"""Head fault tolerance: durable tables + supervised restart.

Parity model: the reference's GCS FT tests — GCS server killed and
restarted with redis-backed tables while raylets re-register
(reference: src/ray/gcs/gcs_server/gcs_table_storage.h,
RayletNotifyGCSRestart; python/ray/tests/test_gcs_fault_tolerance.py).
"""

import os
import signal
import time

import pytest

import ray_tpu


@pytest.fixture
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def _kill_head(rt):
    pid = rt._head_proc.pid
    os.kill(pid, signal.SIGKILL)
    return pid


def _wait_head_respawn(rt, old_pid, timeout=30):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        proc = rt._head_proc
        if proc.pid != old_pid and proc.poll() is None:
            return
        time.sleep(0.2)
    raise TimeoutError("head did not respawn")


def test_head_kill9_pending_gets_complete(cluster):
    """Tasks already pushed to workers complete across a head crash: the
    completion path is worker->owner direct and never touches the head."""

    @ray_tpu.remote
    def slow(i):
        time.sleep(3)
        return i * 2

    refs = [slow.remote(i) for i in range(4)]
    time.sleep(0.5)  # let the pushes land on workers
    old_pid = _kill_head(cluster)
    # Pending gets resolve while the head is down/restarting.
    assert ray_tpu.get(refs, timeout=120) == [0, 2, 4, 6]
    _wait_head_respawn(cluster, old_pid)


def test_head_restart_preserves_actors_kv_and_serves_new_work(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.options(name="survivor").remote()
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 1
    cluster.kv_put("durable_key", b"durable_value")

    old_pid = _kill_head(cluster)
    _wait_head_respawn(cluster, old_pid)
    time.sleep(2.0)  # node re-registration rides the next heartbeat NACK

    # Actor state survives (the actor PROCESS never died; the restarted
    # head recovered its directory entry from the durable tables).
    assert ray_tpu.get(c.inc.remote(), timeout=60) == 2
    handle = ray_tpu.get_actor("survivor")
    assert ray_tpu.get(handle.inc.remote(), timeout=60) == 3
    # KV survives.
    assert cluster.kv_get("durable_key") == b"durable_value"

    # NEW work schedules after restart (nodes re-registered, leases flow).
    @ray_tpu.remote
    def ping():
        return "alive"

    assert ray_tpu.get([ping.remote() for _ in range(8)],
                       timeout=120) == ["alive"] * 8

    # New actors can be created after restart too.
    c2 = Counter.remote()
    assert ray_tpu.get(c2.inc.remote(), timeout=60) == 1
