"""Worker-side pubsub + versioned delta resource sync (reference analog:
src/ray/pubsub/ publisher/subscriber tests; ray_syncer versioned-view
semantics, common/ray_syncer/ray_syncer.h:83)."""

import time

import pytest

import ray_tpu
from ray_tpu.util import pubsub


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_publish_subscribe_roundtrip(cluster):
    got = []
    sub = pubsub.subscribe("t-chan", got.append)
    try:
        pubsub.publish("t-chan", {"k": 1})
        deadline = time.time() + 10
        while not got and time.time() < deadline:
            time.sleep(0.05)
        assert got == [{"k": 1}]
    finally:
        sub.unsubscribe()
    # After unsubscribe, publishes stop arriving.
    pubsub.publish("t-chan", {"k": 2})
    time.sleep(0.5)
    assert got == [{"k": 1}]


def test_worker_side_publish(cluster):
    """A TASK publishes; the driver's subscriber receives — worker-side
    publishers parity (reference: per-worker publishers)."""
    got = []
    sub = pubsub.subscribe("from-worker", got.append)
    try:
        @ray_tpu.remote
        def announce(v):
            from ray_tpu.util import pubsub as p

            p.publish("from-worker", {"value": v})
            return True

        assert ray_tpu.get(announce.remote(42), timeout=60)
        deadline = time.time() + 10
        while not got and time.time() < deadline:
            time.sleep(0.05)
        assert got == [{"value": 42}]
    finally:
        sub.unsubscribe()


def test_node_membership_channel(cluster):
    """The built-in NODE channel reports membership changes."""
    events = []
    sub = pubsub.subscribe("NODE", events.append)
    try:
        node = cluster.add_node(num_cpus=1)
        deadline = time.time() + 30
        while time.time() < deadline:
            if any(e.get("event") == "added" for e in events):
                break
            time.sleep(0.1)
        assert any(e.get("event") == "added" for e in events), events
        cluster.remove_node(node)
        deadline = time.time() + 30
        while time.time() < deadline:
            if any(e.get("event") == "removed" for e in events):
                break
            time.sleep(0.1)
        assert any(e.get("event") == "removed" for e in events), events
    finally:
        sub.unsubscribe()


# ------------------------------------------------------------ delta sync


def test_heartbeat_delta_protocol_unit():
    """Unit-level protocol check against the head handler: full snapshot,
    in-order delta, version-gap NACK, resync recovery."""
    from ray_tpu.cluster.head import HeadServer

    head = HeadServer(port=0)
    try:
        head.rpc_register_node(None, "n1", "127.0.0.1:1", {"CPU": 4.0},
                               {}, "store")
        # Full snapshot at version 0.
        assert head.rpc_heartbeat(None, "n1", {"CPU": 4.0}, 0, False) is True
        # Delta applies only the changed key.
        assert head.rpc_heartbeat(None, "n1", {"CPU": 2.0}, 1, True) is True
        view = [n for n in head.rpc_list_nodes(None)
                if n["node_id"] == "n1"][0]
        assert view["available"] == {"CPU": 2.0}
        # Version gap (lost beat): NACK with resync.
        assert head.rpc_heartbeat(None, "n1", {"CPU": 1.0}, 5, True) \
            == "resync"
        # View unchanged by the rejected delta.
        view = [n for n in head.rpc_list_nodes(None)
                if n["node_id"] == "n1"][0]
        assert view["available"] == {"CPU": 2.0}
        # Recovery: full snapshot at any version re-syncs.
        assert head.rpc_heartbeat(None, "n1", {"CPU": 1.0, "TPU": 8.0},
                                  5, False) is True
        view = [n for n in head.rpc_list_nodes(None)
                if n["node_id"] == "n1"][0]
        assert view["available"] == {"CPU": 1.0, "TPU": 8.0}
        # Delta chain continues from the resynced version.
        assert head.rpc_heartbeat(None, "n1", {"TPU": 4.0}, 6, True) is True
        view = [n for n in head.rpc_list_nodes(None)
                if n["node_id"] == "n1"][0]
        assert view["available"] == {"CPU": 1.0, "TPU": 4.0}
    finally:
        head.shutdown()


def test_scheduler_sees_delta_synced_resources(cluster):
    """End-to-end: the head's availability view stays correct under the
    node's delta heartbeats (tasks consume and release CPU)."""
    @ray_tpu.remote
    def hold(t):
        import time as _t

        _t.sleep(t)
        return 1

    refs = [hold.remote(1.0) for _ in range(4)]
    assert ray_tpu.get(refs, timeout=60) == [1] * 4
    # After completion + a couple of heartbeats, availability returns to
    # the full CPU count in the head's view.
    deadline = time.time() + 15
    while time.time() < deadline:
        avail = ray_tpu.available_resources().get("CPU", 0)
        if avail >= 4.0:
            break
        time.sleep(0.2)
    assert ray_tpu.available_resources().get("CPU", 0) >= 4.0
