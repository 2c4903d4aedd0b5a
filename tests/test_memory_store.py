"""The owner's in-process memory store (core/memory_store.py): blocking
gets wake on the put of THEIR id only.

A chain of N dependent tasks parks one `rpc_get_object` thread per
pending link on the owner, each waiting for a different id. Before PR 31
all of them slept on one store-wide condition and every put woke every
one of them: 3,000 links took 535 s (178 ms a link) where they now take
about 7 s. The cluster side of that is tests/test_dask_backend.py; this
file holds the store to it without a cluster.
"""

import threading

import pytest

from ray_tpu.core.ids import ObjectID
from ray_tpu.core.memory_store import MemoryStore
from ray_tpu.exceptions import GetTimeoutError


def _oid(i: int) -> ObjectID:
    return ObjectID(i.to_bytes(4, "big") * 7)


def test_get_returns_what_is_there_and_times_out_on_what_is_not():
    store = MemoryStore()
    store.put(_oid(1), "a")
    assert [r.value for r in store.get([_oid(1)], 0)] == ["a"]
    with pytest.raises(GetTimeoutError):
        store.get([_oid(2)], 0)
    with pytest.raises(GetTimeoutError):
        store.get([_oid(1), _oid(2)], 0.05)
    # A timed-out waiter leaves no callback behind.
    assert not store._callbacks


@pytest.mark.parametrize("how", ["put", "put_batch"])
def test_blocked_get_wakes_on_its_put(how):
    store = MemoryStore()
    out = []
    t = threading.Thread(
        target=lambda: out.append(store.get([_oid(1), _oid(2)], 30)))
    t.start()
    if how == "put":
        store.put(_oid(2), "two")
        store.put(_oid(1), ValueError("one"), is_exception=True)
    else:
        store.put_batch([(_oid(2), "two", False),
                         (_oid(1), ValueError("one"), True)])
    t.join(30)
    assert not t.is_alive()
    first, second = out[0]
    assert first.is_exception and second.value == "two"


class _CountingLock:
    """The store's lock, counting acquisitions (`with` and the condition's
    own acquire/release both come through here)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acquired = 0

    def acquire(self, *a, **kw):
        got = self._lock.acquire(*a, **kw)
        if got:
            self.acquired += 1  # under the lock itself
        return got

    def release(self):
        self._lock.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()


def test_a_put_wakes_only_its_own_waiter():
    """W threads wait for W different ids, which then arrive one by one:
    the store's lock is taken O(W) times in all, not O(W^2) (every put
    waking every waiter to re-check and sleep again)."""
    w = 200
    store = MemoryStore()
    lock = store._lock = _CountingLock()
    store._cv = threading.Condition(lock)
    results = [None] * w
    parked = threading.Semaphore(0)

    def waiter(i):
        parked.release()
        results[i] = store.get([_oid(i)], 60)[0].value

    threads = [threading.Thread(target=waiter, args=(i,)) for i in range(w)]
    for t in threads:
        t.start()
    for _ in range(w):
        parked.acquire()
    while len(store._callbacks) < w:  # every waiter registered its event
        threading.Event().wait(0.01)
    before = lock.acquired
    for i in range(w):
        store.put(_oid(i), i)
    for t in threads:
        t.join(60)
    assert results == list(range(w))
    # One acquisition per put, and none the waiters need to make: well
    # under the w * w / 2 a store-wide wake costs.
    assert lock.acquired - before <= 4 * w, lock.acquired - before
