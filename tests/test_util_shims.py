"""multiprocessing.Pool shim + joblib backend + collective p2p
(reference test model: python/ray/tests/test_multiprocessing.py,
util/joblib tests, util/collective p2p tests)."""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def _sq(x):
    return x * x


def _addmul(a, b):
    return a * 10 + b


def test_pool_map_variants(cluster):
    from ray_tpu.util.multiprocessing import Pool

    with Pool(processes=4) as p:
        assert p.map(_sq, range(20)) == [i * i for i in range(20)]
        assert p.starmap(_addmul, [(1, 2), (3, 4)]) == [12, 34]
        assert list(p.imap(_sq, range(10), chunksize=3)) == [
            i * i for i in range(10)]
        assert sorted(p.imap_unordered(_sq, range(10), chunksize=2)) == \
            sorted(i * i for i in range(10))
        r = p.apply_async(_addmul, (5, 6))
        assert r.get(timeout=60) == 56
        assert p.apply(_sq, (9,)) == 81
    with pytest.raises(ValueError):
        p.map(_sq, [1])  # closed


def test_joblib_backend(cluster):
    joblib = pytest.importorskip("joblib")
    from ray_tpu.util.joblib_backend import register_ray_tpu

    register_ray_tpu()
    with joblib.parallel_backend("ray_tpu", n_jobs=4):
        out = joblib.Parallel()(joblib.delayed(_sq)(i) for i in range(12))
    assert out == [i * i for i in range(12)]


def test_collective_p2p_send_recv(cluster):
    from ray_tpu.util import collective as col

    @ray_tpu.remote(num_cpus=0)
    class Peer:
        def __init__(self, rank):
            col.init_collective_group(2, rank, "p2p-gang")
            self.rank = rank

        def run(self):
            if self.rank == 0:
                col.send(np.arange(4.0), 1, "p2p-gang", tag=7)
                return col.recv(1, "p2p-gang", tag=8).tolist()
            got = col.recv(0, "p2p-gang", tag=7)
            col.send(got * 2, 0, "p2p-gang", tag=8)
            return got.tolist()

    peers = [Peer.remote(i) for i in range(2)]
    r0, r1 = ray_tpu.get([p.run.remote() for p in peers], timeout=120)
    assert r1 == [0.0, 1.0, 2.0, 3.0]
    assert r0 == [0.0, 2.0, 4.0, 6.0]
    for p in peers:
        ray_tpu.kill(p)
    ray_tpu.kill(ray_tpu.get_actor("rtpu-collective-p2p-gang"))


def test_collective_p2p_same_tag_queues(cluster):
    """Back-to-back sends with ONE tag queue FIFO (no clobber/hang)."""
    from ray_tpu.util import collective as col

    @ray_tpu.remote(num_cpus=0)
    class P:
        def __init__(self, rank):
            col.init_collective_group(2, rank, "fifo-gang")
            self.rank = rank

        def run(self):
            if self.rank == 0:
                for i in range(4):
                    col.send(np.array([i]), 1, "fifo-gang")
                return True
            return [int(col.recv(0, "fifo-gang")[0]) for _ in range(4)]

    a, b = P.remote(0), P.remote(1)
    ok, got = ray_tpu.get([a.run.remote(), b.run.remote()], timeout=120)
    assert got == [0, 1, 2, 3]
    for p in (a, b):
        ray_tpu.kill(p)
    ray_tpu.kill(ray_tpu.get_actor("rtpu-collective-fifo-gang"))


def test_pool_bounds_inflight_and_empty(cluster):
    from ray_tpu.util.multiprocessing import Pool

    with Pool(processes=2) as p:
        # Empty iterable: immediately-ready empty result (stdlib shape).
        r = p.map_async(_sq, [])
        assert r.ready() and r.get(timeout=10) == []
        # successful() raises while pending (stdlib contract).
        slow = p.apply_async(__import__("time").sleep, (1.5,))
        import pytest as _pytest

        if not slow.ready():
            with _pytest.raises(ValueError):
                slow.successful()
        slow.wait(timeout=30)
        # Windowed submission: in-flight never exceeds `processes`.
        res = p.map_async(_sq, range(40), chunksize=1)
        res._pump(block=False)
        assert len(res._refs) <= 2
        assert res.get(timeout=120) == [i * i for i in range(40)]
