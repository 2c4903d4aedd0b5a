"""Owner-routed lease blocks + sharded directory delta sync.

The steady-state head bypass: after the first head-mediated pick for a
scheduling key, the head grants the owner a (node, count, TTL) lease
block and repeat dispatch goes node-direct. These tests cover the full
block lifecycle (grant -> node-direct dispatch -> exhaustion renew ->
revoke on drain/death -> fallback), the no-double-grant memo, the
RTPU_DEBUG_RES lease census draining to zero, and the cursor-journal
directory sync (delta replay and snapshot rebase after a head restart
must rehydrate the directory identically to the PR 8 full republish).

Everything runs on simulated nodes (tier-1: no native store, no worker
processes) — which is exactly the surface bench.py --scale profiles.
"""

from __future__ import annotations

import threading
import time
import uuid
from types import SimpleNamespace

from ray_tpu.cluster.protocol import ClientPool
from ray_tpu.core import cluster_core as cc
from ray_tpu.core.cluster_runtime import SimulatedCluster
from ray_tpu.core.config import GLOBAL_CONFIG as cfg

OWNER = "owner:test"
CPU1 = {"CPU": 1.0}


def _grant(sim, block_id=None, owner=OWNER, resources=CPU1):
    block_id = block_id or uuid.uuid4().hex
    got = sim.client.call("lease_block_grant", block_id, owner,
                          resources, None, None, timeout=10)
    return block_id, got


def _node_by_id(sim, node_id):
    return next(n for n in sim.nodes if n.node_id == node_id)


# ------------------------------------------------------------ lifecycle


def test_grant_installs_budget_and_node_direct_dispatch_drains_it():
    # Size the block to node capacity (CPU 8.0): every admitted dispatch
    # must also FIT, or the node declines and credits the unit back.
    old_size = cfg.lease_block_size
    cfg.set("lease_block_size", 4)
    sim = SimulatedCluster(2, resources={"CPU": 8.0})
    pool = ClientPool()
    try:
        sim.wait_registered(30)
        bid, got = _grant(sim)
        assert got is not None
        node_id, node_addr, size, ttl_ms = got
        assert size == cfg.lease_block_size and ttl_ms > 0
        nm = _node_by_id(sim, node_id)
        assert nm._lease_blocks[bid]["remaining"] == size
        # Node-direct dispatch against the block: no head involvement.
        leases = []
        for _ in range(size):
            granted = pool.get(node_addr).call(
                "request_lease", CPU1, True, None, uuid.uuid4().hex,
                OWNER, None, None, bid, timeout=10)
            assert isinstance(granted, tuple) and len(granted) == 2
            leases.append(granted)
        assert nm._lease_blocks[bid]["remaining"] == 0
        # Exhausted: the node stops honoring it, owner must renegotiate.
        over = pool.get(node_addr).call(
            "request_lease", CPU1, True, None, uuid.uuid4().hex,
            OWNER, None, None, bid, timeout=10)
        assert over == {"block_revoked": True}
        for _w, lease_id in leases:
            assert pool.get(node_addr).call("return_lease", lease_id,
                                            timeout=10)
    finally:
        cfg.set("lease_block_size", old_size)
        pool.close_all()
        sim.shutdown()


def test_same_block_id_grant_is_memoized_no_double_grant():
    sim = SimulatedCluster(2, resources={"CPU": 4.0})
    try:
        sim.wait_registered(30)
        bid, first = _grant(sim)
        _, second = _grant(sim, block_id=bid)  # retry (lost reply)
        assert first == second
        assert len(sim.head._lease_blocks) == 1
        nm = _node_by_id(sim, first[0])
        # Re-install on the node is a no-op: budget never doubles.
        assert nm._lease_blocks[bid]["remaining"] == first[2]
    finally:
        sim.shutdown()


def test_drain_revokes_blocks_at_head_and_node():
    sim = SimulatedCluster(2, resources={"CPU": 4.0})
    pool = ClientPool()
    try:
        sim.wait_registered(30)
        bid, got = _grant(sim)
        node_id, node_addr = got[0], got[1]
        nm = _node_by_id(sim, node_id)
        sim.client.call("drain_node", node_id, timeout=10)
        assert sim.head._lease_blocks == {}
        assert sim.head._node_blocks == {} and sim.head._owner_blocks == {}
        # The drained-but-alive node was TOLD: it stops admitting NOW,
        # and an owner's in-flight dispatch falls back to a head pick.
        assert bid not in nm._lease_blocks
        granted = pool.get(node_addr).call(
            "request_lease", CPU1, True, None, uuid.uuid4().hex,
            OWNER, None, None, bid, timeout=10)
        assert granted == {"block_revoked": True}
    finally:
        pool.close_all()
        sim.shutdown()


def test_node_death_scrubs_head_tables_and_ttl_reaps_node_side():
    sim = SimulatedCluster(2, resources={"CPU": 4.0})
    try:
        sim.wait_registered(30)
        old_ttl = cfg.lease_block_ttl_ms
        cfg.set("lease_block_ttl_ms", 50)
        try:
            bid, got = _grant(sim)
            node_id = got[0]
            nm = _node_by_id(sim, node_id)
            with sim.head._lock:
                sim.head._nodes[node_id].alive = False
            sim.head._on_node_dead(node_id)
            assert sim.head._lease_blocks == {}
            assert node_id not in sim.head._node_blocks
            # No notify on death (nothing to dial) — the node's own TTL
            # sweep is the backstop that releases the admission budget.
            time.sleep(0.1)
            nm._sweep_expired_lease_blocks()
            assert bid not in nm._lease_blocks
        finally:
            cfg.set("lease_block_ttl_ms", old_ttl)
    finally:
        sim.shutdown()


def test_worker_death_revokes_owned_blocks():
    sim = SimulatedCluster(1, resources={"CPU": 4.0})
    try:
        sim.wait_registered(30)
        bid, got = _grant(sim, owner="worker:dead")
        nm = _node_by_id(sim, got[0])
        sim.client.call("worker_dead_at", "worker:dead", timeout=10)
        assert sim.head._lease_blocks == {}
        assert bid not in nm._lease_blocks  # head dialed the node
    finally:
        sim.shutdown()


def test_lease_census_drains_to_zero(monkeypatch):
    """Blocks are leases: the RTPU_DEBUG_RES registry must balance —
    every install matched by a revoke/expiry, every lease returned."""
    monkeypatch.setenv("RTPU_DEBUG_RES", "1")
    from ray_tpu.devtools import res_debug

    res_debug.reset()
    sim = SimulatedCluster(2, resources={"CPU": 8.0})
    pool = ClientPool()
    try:
        sim.wait_registered(30)
        bids = []
        for _ in range(3):
            bid, got = _grant(sim)
            bids.append((bid, got))
        assert res_debug.outstanding("lease_block").get(
            "lease_block", 0) == 3
        _, (node_id, node_addr, _s, _t) = bids[0]
        granted = pool.get(node_addr).call(
            "request_lease", CPU1, True, None, uuid.uuid4().hex,
            OWNER, None, None, bids[0][0], timeout=10)
        assert isinstance(granted, tuple)
        pool.get(node_addr).call("return_lease", granted[1], timeout=10)
        for bid, _got in bids:
            assert sim.client.call("lease_block_revoke", bid, timeout=10)
        assert res_debug.outstanding("lease_block").get(
            "lease_block", 0) == 0
        census = sim.client.call("cluster_leases", timeout=30)
        for entry in census.values():
            assert entry.get("leases") == []
    finally:
        pool.close_all()
        sim.shutdown()
        res_debug.reset()


# --------------------------------------------------- owner dispatch path


def _fake_core(pool, negotiated):
    return SimpleNamespace(
        _lease_lock=threading.Lock(),
        _pool=pool,
        owner_addr=OWNER,
        dispatch_stats={"head_picks": 0, "block_grants": 0,
                        "block_dispatches": 0, "block_fallbacks": 0},
        _revoke_block_async=lambda bid: negotiated.append(("revoke", bid)),
        _negotiate_block=lambda kq, sample, prev=None: negotiated.append(
            ("renew", prev.block_id if prev else None)),
    )


def _kq_with_block(bid, node_id, node_addr, size, ttl_ms):
    kq = SimpleNamespace(key=("f", "sig"), block=None, block_pending=False)
    kq.block = cc._LeaseBlock(bid, node_id, node_addr, size, ttl_ms)
    return kq


def _sample():
    return SimpleNamespace(resources=dict(CPU1), strategy=None,
                           runtime_env=None)


def test_owner_block_dispatch_exhaustion_renew_and_fallback():
    old_size = cfg.lease_block_size
    cfg.set("lease_block_size", 4)  # fits the node's CPU 8.0
    sim = SimulatedCluster(1, resources={"CPU": 8.0})
    pool = ClientPool()
    try:
        sim.wait_registered(30)
        bid, got = _grant(sim)
        node_id, node_addr, size, ttl_ms = got
        events = []
        fake = _fake_core(pool, events)
        kq = _kq_with_block(bid, node_id, node_addr, size, ttl_ms)
        sample = _sample()
        leases = []
        for _ in range(size):
            lease = cc.ClusterCore._request_lease_via_block(
                fake, kq, sample)
            assert lease is not None and lease.node_id == node_id
            leases.append(lease)
        assert fake.dispatch_stats["block_dispatches"] == size
        assert fake.dispatch_stats["head_picks"] == 0
        # Low-water renewal fired off the dispatch path (a daemon
        # thread), exactly once — the renewing flag dedupes it.
        deadline = time.monotonic() + 5
        while (time.monotonic() < deadline
               and ("renew", bid) not in events):
            time.sleep(0.02)
        assert events.count(("renew", bid)) == 1
        # Owner-side exhaustion: block dropped, head-revoke queued, the
        # caller falls back to the head-mediated path.
        assert cc.ClusterCore._request_lease_via_block(
            fake, kq, sample) is None
        assert kq.block is None
        assert ("revoke", bid) in events
        for lease in leases:
            pool.get(node_addr).call("return_lease", lease.lease_id,
                                     timeout=10)
    finally:
        cfg.set("lease_block_size", old_size)
        pool.close_all()
        sim.shutdown()


def test_owner_dispatch_against_revoked_block_falls_back():
    """Head revoked (drain) while the owner still holds budget: the
    node's {"block_revoked"} reply must drop the block and fall back —
    degrade gracefully, never wrongly."""
    sim = SimulatedCluster(1, resources={"CPU": 8.0})
    pool = ClientPool()
    try:
        sim.wait_registered(30)
        bid, got = _grant(sim)
        node_id, node_addr, size, ttl_ms = got
        sim.client.call("lease_block_revoke", bid, timeout=10)
        events = []
        fake = _fake_core(pool, events)
        kq = _kq_with_block(bid, node_id, node_addr, size, ttl_ms)
        assert cc.ClusterCore._request_lease_via_block(
            fake, kq, _sample()) is None
        assert kq.block is None
        assert fake.dispatch_stats["block_fallbacks"] == 1
        assert fake.dispatch_stats["block_dispatches"] == 0
    finally:
        pool.close_all()
        sim.shutdown()


def test_owner_skips_blocks_for_strategy_tasks():
    events = []
    fake = _fake_core(None, events)
    kq = _kq_with_block("b", "n", "a:1", 4, 10_000)
    sample = SimpleNamespace(resources=dict(CPU1),
                             strategy={"kind": "spread"},
                             runtime_env=None)
    assert cc.ClusterCore._request_lease_via_block(fake, kq, sample) is None
    assert kq.block is not None  # untouched: placement stays head-owned


# ------------------------------------------------- directory delta sync


def _until(pred, timeout_s: float = 10.0) -> None:
    """Poll until ``pred()`` holds or the time is up; the caller asserts."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and not pred():
        time.sleep(0.02)


def _wipe_head_directory(head):
    """Simulate what a head restart loses: directory shards + cursors."""
    for sh in head._dir_shards:
        with sh.lock:
            sh.object_dir.clear()
            sh.node_objects.clear()
            sh.object_sizes.clear()
    with head._dir_cursor_lock:
        head._dir_cursors.clear()


def test_journal_tail_replay_rehydrates_identically():
    """Cursor replay (delta path) after losing head state must rebuild
    the directory EXACTLY as the PR 8 full republish did."""
    sim = SimulatedCluster(1, resources={"CPU": 2.0})
    try:
        sim.wait_registered(30)
        nm = sim.nodes[0]
        oids = [bytes([i]) * 28 for i in range(6)]
        nm.rpc_object_batch(None, [("add", o, 10 + i)
                                   for i, o in enumerate(oids)])
        nm.rpc_object_batch(None, [("rm", oids[0], None)])
        # Both frames applied (six adds, then the remove): waiting for
        # "at least five" could read the directory between the two.
        _until(lambda: len(sim.head._object_dir) == 5
               and oids[0] not in sim.head._object_dir)
        before = sim.head._object_dir
        sizes_before = sim.head._object_sizes
        assert len(before) == 5 and oids[0] not in before
        _wipe_head_directory(sim.head)
        # What _on_head_reregistered does (minus re-register plumbing):
        nm._head_dir_cursor = 0
        nm._republish_needed = True
        nm._try_republish()
        # object_batch is a one-way notify: poll for head-side apply. The
        # node's own heartbeat may see the wiped cursor meanwhile
        # ("dir_resync") and ask for the same replay again, so the flag
        # is held to where it SETTLES, not to an instant.
        _until(lambda: sim.head._object_dir == before
               and not nm._republish_needed)
        assert sim.head._object_dir == before
        assert sim.head._object_sizes == sizes_before
        assert not nm._republish_needed
    finally:
        sim.shutdown()


def test_journal_overflow_falls_back_to_snapshot_rebase():
    """When the bounded journal no longer reaches the head's cursor,
    the republish is a store-filtered snapshot with snapshot=True (head
    scrubs the node's entries first) — same end state."""
    sim = SimulatedCluster(1, resources={"CPU": 2.0})
    old_max = cfg.object_dir_journal_max
    cfg.set("object_dir_journal_max", 4)
    try:
        sim.wait_registered(30)
        nm = sim.nodes[0]
        oids = [bytes([i]) * 28 for i in range(12)]
        # Simulated store stub: make the mirror consider them resident.
        resident = {o for o in oids}
        nm.store = SimpleNamespace(
            contains=lambda oid: oid.binary() in resident)
        nm.rpc_object_batch(None, [("add", o, 7) for o in oids])
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and len(sim.head._object_dir) < 12):
            time.sleep(0.05)
        before = sim.head._object_dir
        assert len(before) == 12
        _wipe_head_directory(sim.head)
        nm._head_dir_cursor = 0  # journal floor is way past 1 now
        nm._republish_needed = True
        nm._try_republish()
        _until(lambda: sim.head._object_dir == before
               and not nm._republish_needed)
        assert sim.head._object_dir == before
        assert not nm._republish_needed
    finally:
        cfg.set("object_dir_journal_max", old_max)
        sim.shutdown()


def test_heartbeat_detects_cursor_gap_and_heals():
    """A dropped object_batch frame (or restarted head) surfaces as a
    ("dir_resync", cursor) heartbeat ack; the node replays only the
    tail past the head's cursor on its next lap."""
    sim = SimulatedCluster(1, resources={"CPU": 2.0})
    try:
        sim.wait_registered(30)
        nm = sim.nodes[0]
        oids = [bytes([i]) * 28 for i in range(4)]
        nm.rpc_object_batch(None, [("add", o, 5) for o in oids])
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and len(sim.head._object_dir) < 4):
            time.sleep(0.05)
        assert len(sim.head._object_dir) == 4
        _wipe_head_directory(sim.head)
        assert sim.head._object_dir == {}
        deadline = time.monotonic() + 15
        healed = False
        while time.monotonic() < deadline:
            nm._hb_wake.set()
            if len(sim.head._object_dir) == 4:
                healed = True
                break
            time.sleep(0.1)
        assert healed, "dir_resync heartbeat ack did not trigger replay"
        with sim.head._dir_cursor_lock:
            assert sim.head._dir_cursors[nm.node_id] == nm._dir_seq
    finally:
        sim.shutdown()


def test_scheduler_stats_count_blocks():
    sim = SimulatedCluster(1, resources={"CPU": 4.0})
    try:
        sim.wait_registered(30)
        _grant(sim)
        stats = sim.client.call("scheduler_stats", timeout=10)
        assert stats["lease_blocks"] == 1
    finally:
        sim.shutdown()
