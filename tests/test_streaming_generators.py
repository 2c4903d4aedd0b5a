"""Streaming-generator tasks: num_returns="streaming" (reference test
model: python/ray/tests/test_streaming_generator.py) and the Data wiring
(generator read tasks streaming blocks incrementally)."""

import time

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rdata


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def test_streaming_task_yields_refs_in_order(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    out = gen.remote(7)
    assert isinstance(out, ray_tpu.ObjectRefGenerator)
    vals = [ray_tpu.get(ref, timeout=30) for ref in out]
    assert vals == [0, 10, 20, 30, 40, 50, 60]


def test_streaming_consumes_before_producer_finishes(cluster):
    """The first item must be gettable while the producer still runs —
    the memory-stability property streaming exists for."""

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(5):
            yield i
            time.sleep(0.4)

    t0 = time.perf_counter()
    gen = slow_gen.remote()
    first = ray_tpu.get(next(gen), timeout=30)
    first_latency = time.perf_counter() - t0
    assert first == 0
    # Producer takes ~2s total; the first item must arrive well before.
    assert first_latency < 1.5, first_latency
    rest = [ray_tpu.get(r, timeout=30) for r in gen]
    assert rest == [1, 2, 3, 4]


def test_streaming_large_items_go_to_store(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def big_gen():
        for i in range(4):
            yield np.full(300_000, i, dtype=np.int64)  # 2.4MB each

    totals = [int(ray_tpu.get(r, timeout=60)[0]) for r in big_gen.remote()]
    assert totals == [0, 1, 2, 3]


def test_streaming_mid_stream_error_surfaces_after_items(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def bad_gen():
        yield 1
        yield 2
        raise ValueError("boom at 2")

    gen = bad_gen.remote()
    assert ray_tpu.get(next(gen), timeout=30) == 1
    assert ray_tpu.get(next(gen), timeout=30) == 2
    with pytest.raises(Exception) as ei:
        next(gen)
    assert "boom" in str(ei.value)


def test_streaming_empty_generator(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        if False:
            yield 1

    assert list(empty.remote()) == []


def test_streaming_backpressure_bounds_producer(cluster):
    """An unconsumed stream must pause its producer: after the consumer
    stops, the producer may run at most ~STREAM_AHEAD_MAX items ahead."""

    @ray_tpu.remote(num_returns="streaming")
    def firehose(n):
        for i in range(n):
            yield i

    gen = firehose.remote(10_000)
    first = ray_tpu.get(next(gen), timeout=30)
    assert first == 0
    time.sleep(1.5)  # producer would finish all 10k in this time unthrottled
    st = cluster._streams.get(gen.task_id().binary())
    assert st is not None
    with st.cv:
        received = st.received
    # consumed=1; producer must have paused near 1 + window (64) + flush
    # slack — nowhere near 10k.
    assert received <= 1 + 64 + 80, received
    rest = [ray_tpu.get(r, timeout=60) for r in gen]
    assert rest == list(range(1, 10_000))


def test_streaming_abandoned_generator_releases(cluster):
    """Dropping the generator mid-stream cancels the producer and frees
    undelivered items (no unbounded owner-side growth)."""

    @ray_tpu.remote(num_returns="streaming")
    def infinite():
        i = 0
        while True:
            yield i
            i += 1

    gen = infinite.remote()
    tid = gen.task_id()
    assert ray_tpu.get(next(gen), timeout=30) == 0
    gen.close()
    assert tid.binary() not in cluster._streams
    # Worker-side generator must stop: the inflight entry drains (the
    # task sends stream_end after observing the cancel).
    deadline = time.time() + 30
    while time.time() < deadline:
        with cluster._inflight_lock:
            if tid.binary() not in cluster._inflight:
                break
        time.sleep(0.2)
    with cluster._inflight_lock:
        assert tid.binary() not in cluster._inflight, \
            "producer never observed abandonment"


def test_data_generator_read_tasks_stream_blocks(cluster):
    """from_generators: one read task yields many blocks; the pipeline
    sees every chunk, maps fuse over them, memory never holds the whole
    source (10 chunks x 100 rows from 2 tasks)."""

    def source(base):
        def gen():
            for c in range(10):
                yield {"v": np.arange(100) + base + c * 100}
        return gen

    ds = rdata.from_generators([source(0), source(10_000)],
                               parallelism=2)
    ds = ds.map_batches(lambda b: {"v": b["v"] * 2})
    rows = [r["v"] for r in ds.iter_rows()]
    assert len(rows) == 2000
    expect = sorted([(v + c * 100) * 2 for c in range(10)
                     for v in range(100)]
                    + [(v + 10_000 + c * 100) * 2 for c in range(10)
                       for v in range(100)])
    assert sorted(rows) == expect


def test_data_streaming_source_larger_than_memory_budget(cluster,
                                                         monkeypatch):
    """A 40MB generator source flows through a pipeline with an 8MB
    memory budget: completes exactly, never materializing the source."""
    from ray_tpu.core.config import GLOBAL_CONFIG as cfg

    monkeypatch.setitem(cfg._values, "data_memory_budget_bytes",
                        8 * 1024 * 1024)

    def source():
        for _ in range(20):
            yield {"x": np.ones(250_000, dtype=np.float64)}  # 2MB each

    ds = rdata.from_generators([source]).map_batches(
        lambda b: {"x": b["x"] * 3})
    total_rows = 0
    total_sum = 0.0
    for batch in ds.iter_batches(batch_size=None):
        total_rows += len(batch["x"])
        total_sum += float(batch["x"].sum())
    assert total_rows == 20 * 250_000
    assert abs(total_sum - 3.0 * total_rows) < 1e-3
