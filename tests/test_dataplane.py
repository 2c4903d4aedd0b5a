"""Data-plane tests: sharded shm store (multi-writer correctness, layout
guard) and the scatter-gather RPC framing (zero-copy frames, recv_into
sinks, chaos tolerance).

Store-backed tests load the native library, which is built from
`_cpp/shm_store.cc` on first use; where that cannot be done they fail by
name (conftest's `native_store`).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import threading
import time

import numpy as np
import pytest

from ray_tpu.core import shm_store
from ray_tpu.core.config import GLOBAL_CONFIG as cfg


def _oid(i: int, salt: int = 0):
    from ray_tpu.core.ids import ObjectID

    return ObjectID(bytes([salt % 256]) + i.to_bytes(8, "little") + b"\0" * 19)


# --------------------------------------------------------------------------
# store: layout guard
# --------------------------------------------------------------------------


def test_layout_version_matches(native_store):
    lib = shm_store._load_lib()
    assert int(lib.rtpu_lib_layout_version()) == shm_store._LAYOUT_VERSION


def test_open_missing_store_mentions_rebuild(native_store):
    with pytest.raises(OSError, match="layout version"):
        shm_store.ShmStore.open("/rtpu_test_definitely_missing")


# --------------------------------------------------------------------------
# store: sharded arena
# --------------------------------------------------------------------------


def test_sharded_store_basic_and_fallthrough(native_store):
    # 640 MB / 8 shards ~= 76 MB sub-arenas (>= the 64 MB floor).
    store = shm_store.ShmStore.create("/rtpu_test_shard", 640 << 20,
                                      prefault=False)
    try:
        assert store.n_shards > 1, "store this size should shard"
        # Objects near the sub-arena size force cross-shard fallthrough:
        # one per shard fits, a second in the same sub-arena cannot.
        nbytes = 60 << 20
        n = min(6, store.n_shards)
        payloads = {}
        for i in range(n):
            data = bytes([i * 37 % 256]) * 64
            store.put_bytes(_oid(i), [data, b"\0" * (nbytes - 64)])
            payloads[i] = data
        used, cap, n_objects, _ = store.stats()
        assert n_objects == n
        assert used >= n * nbytes
        for i in range(n):
            buf = store.get(_oid(i))
            assert buf is not None
            assert bytes(buf.buffer[:64]) == payloads[i]
            assert len(buf.buffer) == nbytes
            buf.release()
        for i in range(n):
            assert store.delete(_oid(i))
        used, _, n_objects, _ = store.stats()
        assert n_objects == 0
        assert used == 0
    finally:
        store.close()


def test_oversized_object_fails_fast_with_shard_hint(native_store):
    store = shm_store.ShmStore.create("/rtpu_test_big", 640 << 20,
                                      prefault=False)
    try:
        if store.n_shards < 2:
            pytest.skip("store did not shard on this config")
        t0 = time.monotonic()
        with pytest.raises(shm_store.ShmStoreFullError, match="sub-arena"):
            store.create_buffer(_oid(1), store.max_object_bytes + 1)
        # Fail-fast: no spill/evict/sleep laps for a can-never-fit object.
        assert time.monotonic() - t0 < 1.0
    finally:
        store.close()


def test_reclaim_pending_never_touches_live_objects(native_store):
    """reclaim_pending is the dead-creator rescue: it must refuse sealed
    objects, in-write (allocated) objects, and absent keys — only a true
    PENDING placeholder (unreachable from Python without a mid-create
    crash) is reclaimable."""
    store = shm_store.ShmStore.create("/rtpu_test_reclaim", 64 << 20,
                                      prefault=False)
    try:
        assert not store.reclaim_pending(_oid(1))  # absent
        store.put_bytes(_oid(1), b"x" * 1024)
        assert not store.reclaim_pending(_oid(1))  # sealed
        assert store.contains(_oid(1))
        mv = store.create_buffer(_oid(2), 1024)  # allocated, unsealed
        assert not store.reclaim_pending(_oid(2))
        mv[:1] = b"a"
        store.seal(_oid(2))
        assert store.contains(_oid(2))
    finally:
        store.close()


def test_small_store_collapses_to_one_shard(native_store):
    store = shm_store.ShmStore.create("/rtpu_test_tiny", 64 << 20,
                                      prefault=False)
    try:
        assert store.n_shards == 1
        # The full arena (minus block headers) is one allocation's limit.
        mv = store.create_buffer(_oid(7), 48 << 20)
        mv[:4] = b"abcd"
        store.seal(_oid(7))
        assert store.contains(_oid(7))
        store.delete(_oid(7))
    finally:
        store.close()


# --------------------------------------------------------------------------
# store: multi-process concurrency
# --------------------------------------------------------------------------


def _hammer_proc(store_name: str, idx: int, n_objects: int, obj_bytes: int,
                 barrier, q):
    """Writer: put own objects, read back + verify, delete half. Also read
    neighbours' objects when visible (cross-process get path)."""
    try:
        from ray_tpu.core import shm_store

        store = shm_store.ShmStore.open(store_name)
        barrier.wait(timeout=60)
        kept, deleted = [], []
        for i in range(n_objects):
            oid = _oid(i, salt=idx)
            pattern = (idx * 101 + i) % 256
            store.put_bytes(oid, [bytes([pattern]) * 64,
                                  b"\0" * (obj_bytes - 64)])
            buf = store.get(oid, timeout_ms=2000)
            assert buf is not None, f"writer {idx} lost object {i}"
            assert buf.buffer[0] == pattern
            buf.release()
            if i % 2:
                assert store.delete(oid)
                deleted.append(i)
            else:
                kept.append(i)
            # Occasionally read a neighbour's kept object (pin churn).
            if i % 7 == 3:
                nbuf = store.get(_oid(max(0, i - 2), salt=(idx + 1) % 4),
                                 timeout_ms=0)
                if nbuf is not None:
                    nbuf.release()
        # Verify every kept object survived (restore-from-spill included),
        # every deleted one reads absent (no ghosts, no resurrection).
        for i in kept:
            buf = store.get(_oid(i, salt=idx), timeout_ms=5000)
            assert buf is not None, f"writer {idx} kept object {i} is a ghost"
            assert buf.buffer[0] == (idx * 101 + i) % 256, "corrupted"
            buf.release()
        for i in deleted:
            assert not store.contains(_oid(i, salt=idx))
        q.put(("ok", idx, len(kept)))
    except BaseException as e:  # noqa: BLE001 — reported to the parent
        q.put(("err", idx, repr(e)))


def _run_hammer(k: int, n_objects: int, obj_bytes: int, capacity: int,
                name: str):
    store = shm_store.ShmStore.create(name, capacity, prefault=False)
    try:
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        barrier = ctx.Barrier(k)
        procs = [ctx.Process(target=_hammer_proc,
                             args=(name, i, n_objects, obj_bytes, barrier, q))
                 for i in range(k)]
        for p in procs:
            p.start()
        results = []
        deadline = time.monotonic() + 180
        while len(results) < k and time.monotonic() < deadline:
            try:
                results.append(q.get(timeout=5))
            except Exception:
                if not any(p.is_alive() for p in procs):
                    break
        for p in procs:
            p.join(timeout=30)
            assert not p.is_alive(), "hammer writer deadlocked"
        assert len(results) == k, f"only {len(results)}/{k} writers finished"
        errs = [r for r in results if r[0] != "ok"]
        assert not errs, f"writer failures: {errs}"
    finally:
        store.close()


def test_multiprocess_hammer_small(native_store):
    """4 processes x 24 x 1 MB through one 640 MB store (no pressure)."""
    _run_hammer(4, 24, 1 << 20, 640 << 20, "/rtpu_test_hammer_s")


@pytest.mark.slow
def test_multiprocess_hammer_spill_pressure(native_store):
    """4 processes x 60 x 4 MB kept-half through a 640 MB store: live
    bytes approach the arena so the spill path engages; every kept object
    must still read back byte-correct (restore) and every deleted one
    stays deleted (no ghosts)."""
    if not cfg.object_spilling_enabled:
        pytest.skip("spilling disabled in this config")
    _run_hammer(4, 60, 4 << 20, 640 << 20, "/rtpu_test_hammer_p")


# --------------------------------------------------------------------------
# protocol: scatter-gather framing (no native lib needed)
# --------------------------------------------------------------------------


class _EchoHandler:
    def __init__(self):
        self.conns = []

    def rpc_register(self, conn):
        self.conns.append(conn)
        return True

    def rpc_echo(self, conn, x):
        return x

    def rpc_chunk(self, conn, n, fill):
        import pickle

        from ray_tpu.cluster.protocol import BufferLease

        data = np.full(n, fill, np.uint8)
        return BufferLease((n, pickle.PickleBuffer(memoryview(data))),
                           lambda: None)


@pytest.fixture
def rpc_pair():
    from ray_tpu.cluster.protocol import RpcClient, RpcServer

    handler = _EchoHandler()
    server = RpcServer(handler).start()
    client = RpcClient(server.address)
    yield handler, server, client
    client.close()
    server.stop()


def test_scatter_frame_large_roundtrip(rpc_pair):
    """> 4 MB payload rides the scatter form (sendmsg of raw buffers ->
    recv_into) and round-trips byte-identically."""
    _h, _s, client = rpc_pair
    rng = np.random.default_rng(0)
    arr = rng.integers(0, 256, 6 << 20, dtype=np.uint8)
    out = client.call("echo", arr, timeout=60)
    assert isinstance(out, np.ndarray)
    assert out.nbytes == arr.nbytes
    assert np.array_equal(out, arr)
    # Mixed payload: multiple out-of-band buffers + inline smalls.
    payload = {"a": arr[: 1 << 20], "b": arr, "c": [1, "x", b"y" * 100]}
    out = client.call("echo", payload, timeout=60)
    assert np.array_equal(out["a"], arr[: 1 << 20])
    assert np.array_equal(out["b"], arr)
    assert out["c"] == [1, "x", b"y" * 100]


def test_scatter_frame_chaos_roundtrip(rpc_pair):
    """Chaos-dropped requests/responses retry to a byte-identical result
    through the scatter path."""
    _h, _s, client = rpc_pair
    arr = np.arange(5 << 17, dtype=np.int64)  # ~5 MB
    cfg.set("rpc_chaos_failure_prob", 0.3)
    try:
        out = client.retrying_call("echo", arr, timeout=10)
    finally:
        cfg.set("rpc_chaos_failure_prob", 0.0)
    assert np.array_equal(out, arr)


def test_call_into_sink_lands_bytes(rpc_pair):
    """A response buffer of exactly the sink's length lands directly in
    the caller's view (the pulled-chunk zero-staging-copy path)."""
    _h, _s, client = rpc_pair
    n = 2 << 20
    sink = bytearray(n)
    (total, data), landed = client.call_into(
        "chunk", n, 9, sink=memoryview(sink), timeout=30)
    assert landed, "response did not land in the sink"
    assert total == n
    assert sink[0] == 9 and sink[-1] == 9 and sink[n // 2] == 9
    # The decoded buffer IS the sink's memory.
    assert len(data) == n and data[0] == 9


def test_call_into_mismatched_sink_falls_back(rpc_pair):
    _h, _s, client = rpc_pair
    sink = bytearray(100)  # wrong size: reply must use its own buffer
    (total, data), landed = client.call_into(
        "chunk", 1 << 20, 5, sink=memoryview(sink), timeout=30)
    assert not landed
    assert total == 1 << 20 and len(data) == 1 << 20 and data[0] == 5
    assert bytes(sink) == b"\0" * 100


def test_client_pool_upgrades_on_push(rpc_pair):
    """Regression: a cached push-less client must gain a later caller's
    on_push (it silently dropped server pushes before)."""
    from ray_tpu.cluster.protocol import ClientPool

    handler, _s, _c = rpc_pair
    pool = ClientPool()
    try:
        first = pool.get(_s.address)  # opened WITHOUT on_push
        assert first._on_push is None
        got = []
        evt = threading.Event()

        def on_push(method, args):
            got.append((method, args))
            evt.set()

        second = pool.get(_s.address, on_push=on_push)
        assert second is first, "pool must reuse the cached client"
        assert second._on_push is on_push
        second.call("register", timeout=10)
        handler.conns[0].notify("poked", 42)
        assert evt.wait(10), "push was not delivered to the upgraded client"
        assert got == [("poked", (42,))]
    finally:
        pool.close_all()


def test_event_stats_fold_across_threads(rpc_pair):
    from ray_tpu.cluster import protocol

    _h, _s, client = rpc_pair
    before = protocol.get_event_stats().get("echo", {}).get("count", 0)
    threads = [threading.Thread(target=lambda: client.call("echo", 1,
                                                           timeout=10))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    after = protocol.get_event_stats().get("echo", {}).get("count", 0)
    assert after - before == 8
