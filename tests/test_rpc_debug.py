"""RTPU_DEBUG_RPC witness: classification-hole detection, the
duplicate-delivery (at-most-once) audit, the outbox ordering witness,
and the flag-off zero-overhead contract — over real RpcServer/RpcClient
pairs (no cluster, no store; tier-1 everywhere).
"""

from __future__ import annotations

import pickle
import threading

import pytest

from ray_tpu.cluster.protocol import (BufferLease, RpcClient, RpcServer)
from ray_tpu.devtools import rpc_debug


@pytest.fixture
def witness(monkeypatch):
    monkeypatch.setenv("RTPU_DEBUG_RPC", "1")
    rpc_debug.reset()
    yield
    rpc_debug.reset()


class _Handler:
    """Handlers named after REAL classified methods so the fixture
    exercises the production sets: reserve_bundle (idempotent, memoized
    here), new_job_id (acked-retry: dup-exempt by classification),
    ping (read-only), kv_put (declared idempotent — this impl is
    deliberately broken to prove the audit refuses it)."""

    chaos_role = "node"
    extra_retry_safe_rpcs = frozenset({"echo_local"})
    extra_idempotent_rpcs = frozenset({"fetch_chunk_local"})

    def __init__(self, break_kv_put: bool = False):
        self.break_kv_put = break_kv_put
        self.bundles = {}
        self.job_counter = 0
        self.kv = {}
        self.releases = 0
        self.released = threading.Semaphore(0)  # one per lease released

    def rpc_ping(self, conn):
        return "pong"

    def rpc_echo_local(self, conn, x):
        return x

    def rpc_reserve_bundle(self, conn, pg_id, idx, bundle):
        if (pg_id, idx) in self.bundles:
            return True
        self.bundles[(pg_id, idx)] = dict(bundle)
        return True

    def rpc_new_job_id(self, conn):
        self.job_counter += 1
        return self.job_counter

    def rpc_kv_put(self, conn, ns, key, value, overwrite=True):
        if self.break_kv_put:
            self.job_counter += 1
            return self.job_counter  # non-idempotent response: a bug
        self.kv[(ns, key)] = value
        return True

    def rpc_fetch_chunk_local(self, conn, offset, chunk):
        view = memoryview(b"0123456789abcdef")[offset:offset + chunk]

        def release():
            self.releases += 1
            self.released.release()

        return BufferLease((16, pickle.PickleBuffer(view)), release)

    def rpc_totally_new_thing(self, conn):
        return 1


@pytest.fixture
def pair():
    h = _Handler()
    server = RpcServer(h).start()
    client = RpcClient(server.address)
    yield h, client
    client.close()
    server.stop()


class _ChannelHandler:
    """Channel-negotiation handlers shaped like the head's (the PR 19
    cross-node edge surface, classified in protocol.py): register
    overwrites with the same entry, lookup is read-only, unregister of
    an unknown channel holds at True."""

    chaos_role = "node"

    def __init__(self):
        self.channels = {}

    def rpc_channel_register(self, conn, channel_id, addr, owner="",
                             node_id=""):
        self.channels[channel_id] = {"addr": addr, "owner": owner,
                                     "node_id": node_id, "alive": True}
        return True

    def rpc_channel_lookup(self, conn, channel_id):
        ent = self.channels.get(channel_id)
        return dict(ent) if ent is not None else None

    def rpc_channel_unregister(self, conn, channel_id):
        self.channels.pop(channel_id, None)
        return True


# ------------------------------------------------- classification holes


def test_classification_hole_detected(witness, pair):
    h, client = pair
    with pytest.raises(rpc_debug.UnclassifiedRpcError):
        client.call("totally_new_thing", timeout=5)
    kinds = [v["kind"] for v in rpc_debug.violations()]
    assert kinds == ["classification-hole"]


def test_class_local_declaration_fills_hole(witness, pair):
    h, client = pair
    assert client.call("echo_local", 7, timeout=5) == 7
    assert rpc_debug.violations() == []


def test_classified_methods_dispatch_clean(witness, pair):
    h, client = pair
    assert client.call("ping", timeout=5) == "pong"
    assert client.call("new_job_id", timeout=5) == 1
    assert rpc_debug.violations() == []


# -------------------------------------------- duplicate-delivery audit


def test_idempotent_dup_accepted(witness, pair):
    """A properly memoized idempotent handler survives re-delivery:
    the duplicate runs (audited), responses match, no violation."""
    h, client = pair
    assert client.call("reserve_bundle", b"pg", 0, {"CPU": 1},
                       timeout=5) is True
    assert rpc_debug.dup_audit_counts().get("reserve_bundle") == 1
    assert rpc_debug.violations() == []
    # The duplicate really ran against the handler (memo hit, not skip).
    assert h.bundles == {(b"pg", 0): {"CPU": 1}}


def test_non_idempotent_dup_refused(witness):
    """A handler DECLARED idempotent whose duplicate returns a
    different response is a recorded violation — at-most-once is not
    actually held."""
    h = _Handler(break_kv_put=True)
    server = RpcServer(h).start()
    client = RpcClient(server.address)
    try:
        client.call("kv_put", "ns", b"k", b"v", timeout=5)
        kinds = [v["kind"] for v in rpc_debug.violations()]
        assert kinds == ["dup-mismatch"]
        assert rpc_debug.violations()[0]["method"] == "kv_put"
    finally:
        client.close()
        server.stop()


def test_readonly_and_acked_retry_not_dup_audited(witness, pair):
    """new_job_id (acked-retry) legitimately burns an id per delivery;
    ping is read-only — neither is double-delivered."""
    h, client = pair
    client.call("ping", timeout=5)
    assert client.call("new_job_id", timeout=5) == 1
    assert client.call("new_job_id", timeout=5) == 2  # no hidden dups
    assert rpc_debug.dup_audit_counts() == {}
    assert rpc_debug.violations() == []


def test_buffer_lease_dup_compared_and_released(witness, pair):
    """BufferLease responses (pinned shm views): the duplicate's view is
    compared by content then released; the original lease flows on.
    Declared via the class-local extra_idempotent_rpcs set."""
    h, client = pair
    result = client.call("fetch_chunk_local", 0, 8, timeout=5)
    total, buf = result
    assert total == 16 and bytes(buf) == b"01234567"
    assert rpc_debug.dup_audit_counts().get("fetch_chunk_local") == 1
    assert rpc_debug.violations() == []
    # Both deliveries' leases are released: the dup's by the witness, the
    # original's by the response path AFTER the frame went out — the
    # borrowed view must outlive the send, so the server releases behind
    # the reply and the client may hold the reply first. Wait for the
    # releases themselves, not for the reply.
    assert h.released.acquire(timeout=10), "dup's lease never released"
    assert h.released.acquire(timeout=10), "original's lease never released"
    assert not h.released.acquire(timeout=0.2), "a lease released twice"
    assert h.releases == 2


def test_channel_negotiation_dup_delivery_smoke(witness):
    """The channel-negotiation RPCs hold at-most-once under the
    witness's double delivery: a re-delivered register re-applies the
    same entry (same True), unregister of an already-gone channel
    stays True (the state 'not registered' holds), and lookup is
    read-only — never dup-audited."""
    h = _ChannelHandler()
    server = RpcServer(h).start()
    client = RpcClient(server.address)
    try:
        cid = b"c" * 16
        assert client.call("channel_register", cid, "tcp://h:1",
                           "ownerA", "node1", timeout=5) is True
        assert rpc_debug.dup_audit_counts().get("channel_register") == 1
        ent = client.call("channel_lookup", cid, timeout=5)
        assert ent["addr"] == "tcp://h:1" and ent["alive"]
        assert client.call("channel_unregister", cid, timeout=5) is True
        assert client.call("channel_lookup", cid, timeout=5) is None
        assert rpc_debug.dup_audit_counts().get(
            "channel_unregister") == 1
        assert "channel_lookup" not in rpc_debug.dup_audit_counts()
        assert rpc_debug.violations() == []
    finally:
        client.close()
        server.stop()


def test_dup_nth_sampling(witness, pair, monkeypatch):
    monkeypatch.setenv("RTPU_DEBUG_RPC_DUP_NTH", "2")
    h, client = pair
    for i in range(4):
        client.call("reserve_bundle", b"pg", i, {}, timeout=5)
    assert rpc_debug.dup_audit_counts().get("reserve_bundle") == 2
    monkeypatch.setenv("RTPU_DEBUG_RPC_DUP_NTH", "0")
    client.call("reserve_bundle", b"pg", 9, {}, timeout=5)
    assert rpc_debug.dup_audit_counts().get("reserve_bundle") == 2


# --------------------------------------------------- outbox ordering


def test_ordering_inversion_caught(witness):
    e1 = rpc_debug.stamp_outbox("owner:1", [("add", b"o1", 4)])
    e2 = rpc_debug.stamp_outbox("owner:1", [("rm", b"o1", None)])
    # Frames arrive INVERTED at the receiver.
    out2 = rpc_debug.check_outbox("head", e2)
    assert out2 == [("rm", b"o1", None)]  # stamp stripped
    rpc_debug.check_outbox("head", e1)
    kinds = [v["kind"] for v in rpc_debug.violations()]
    assert kinds == ["outbox-inversion"]
    v = rpc_debug.violations()[0]
    assert v["sender"] == "owner:1" and v["receiver"] == "head"


def test_redelivered_frame_caught(witness):
    e1 = rpc_debug.stamp_outbox("node:a", [("add", b"o1", 4)])
    rpc_debug.check_outbox("head", e1)
    rpc_debug.check_outbox("head", list(e1))  # duplicate delivery
    assert [v["kind"] for v in rpc_debug.violations()] == \
        ["outbox-inversion"]


def test_unstamped_frame_caught(witness):
    """With the witness on, every designated outbox sender stamps — an
    unstamped frame came from a path that bypassed the outbox (the
    PR 4 bug class), and the receiver reports it on arrival."""
    out = rpc_debug.check_outbox("head", [("add", b"o1", 4)])
    assert out == [("add", b"o1", 4)]
    assert [v["kind"] for v in rpc_debug.violations()] == \
        ["outbox-unstamped"]


def test_in_order_streams_clean(witness):
    for i in range(5):
        frame = rpc_debug.stamp_outbox("node:a", [("add", bytes([i]), 1)])
        out = rpc_debug.check_outbox("head", frame)
        assert out == [("add", bytes([i]), 1)]
    # Independent (sender, receiver) streams do not interfere.
    other = rpc_debug.stamp_outbox("node:b", [("rm", b"x", None)])
    rpc_debug.check_outbox("head", other)
    assert rpc_debug.violations() == []


# -------------------------------------------------- flag-off contract


def test_flag_off_returns_unwrapped_dispatch(monkeypatch):
    monkeypatch.delenv("RTPU_DEBUG_RPC", raising=False)
    assert not rpc_debug.enabled()
    assert rpc_debug.dispatch_audit("anything") is None
    # Stamping/checking are identity when off.
    entries = [("add", b"o", 1)]
    assert rpc_debug.stamp_outbox("s", entries) is entries


def test_flag_off_unclassified_method_serves(monkeypatch):
    """Without the witness, an unclassified method dispatches exactly
    as before — the contract costs nothing in production."""
    monkeypatch.delenv("RTPU_DEBUG_RPC", raising=False)
    h = _Handler()
    server = RpcServer(h).start()
    client = RpcClient(server.address)
    try:
        assert client.call("totally_new_thing", timeout=5) == 1
    finally:
        client.close()
        server.stop()


def test_recv_seq_streams_bounded_lru(witness):
    """Every respawned peer is a new sender, so the receiver-side
    stream table accretes dead senders over a long chaos run — it is
    now LRU-bounded at 4096 streams (the res-family audit; eviction
    can only relax a monotonicity check, never fabricate a violation).
    LRU by last frame, not insertion order: a busy LIVE stream must
    survive even though it was registered first."""
    for i in range(4096):
        frame = rpc_debug.stamp_outbox(f"node:{i}", [("add", b"o", 1)])
        rpc_debug.check_outbox("head", frame)
    # node:0 — the oldest-INSERTED stream — speaks again (it is live).
    frame = rpc_debug.stamp_outbox("node:0", [("add", b"o", 1)])
    rpc_debug.check_outbox("head", frame)
    # Two fresh senders push the table over the cap twice.
    for i in range(4096, 4098):
        frame = rpc_debug.stamp_outbox(f"node:{i}", [("add", b"o", 1)])
        rpc_debug.check_outbox("head", frame)
    assert rpc_debug.violations() == []
    with rpc_debug._REGISTRY._mu:
        assert len(rpc_debug._REGISTRY.recv_seq) == 4096
        # The live (recently-heard) stream survived; the idle ones
        # registered right after it were evicted instead.
        assert ("node:0", "head") in rpc_debug._REGISTRY.recv_seq
        assert ("node:1", "head") not in rpc_debug._REGISTRY.recv_seq
        assert ("node:2", "head") not in rpc_debug._REGISTRY.recv_seq
        assert ("node:4097", "head") in rpc_debug._REGISTRY.recv_seq
    # And the survivor's high-water mark is intact: a replay of its
    # first frame is still caught as an inversion.
    rpc_debug.check_outbox("head", [(rpc_debug.SEQ_KIND, "node:0", 1)])
    assert any(v["kind"] == "outbox-inversion"
               for v in rpc_debug.violations())
