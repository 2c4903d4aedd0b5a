"""Streaming Dataset executor tier: operator graph construction, bounded
inter-operator queues, and the channel data plane under map stages and
shuffles (reference test model: python/ray/data/tests/
test_streaming_executor.py, test_backpressure_policies.py,
test_streaming_fault_tolerance.py).

The top half is store-free (plan rewriting is pure, queues ride mmap
rings); the cluster half skips cleanly where the native store lib can't
boot a cluster.
"""

import threading
import time
import uuid

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rdata
from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.data._exchange import merge_pieces, partition_rows
from ray_tpu.data._executor import (ChannelMapStage, adapt_plan,
                                    describe_physical)
from ray_tpu.data._queues import ChannelQueue, LocalQueue, QueueStopped
from ray_tpu.data._streaming import (ExecContext, InputOperator,
                                     LimitOperator, optimize_plan)
from ray_tpu.dag.ring import RingChannel


# ------------------------------------------------- physical plan (store-free)

def test_adapt_plan_builds_channel_stages():
    ds = (rdata.range(32)
          .map_batches(lambda b: {"id": b["id"] * 2})
          .map_batches(lambda b: {"id": b["id"] + 1})
          .map_batches(lambda b: {"id": b["id"] * 10}))
    ops = adapt_plan(optimize_plan(ds._ops))
    stages = [op for op in ops if isinstance(op, ChannelMapStage)]
    # Fusion happened BEFORE the physical rewrite: one lane fleet runs
    # the whole fused chain, not one per map.
    assert len(stages) == 1
    assert len(stages[0].payload["stages"]) == 3
    assert stages[0].lanes >= 1
    desc = describe_physical(ops)
    assert desc.startswith("channel_map[") and "+" in desc, desc


def test_limit_pushdown_survives_adapt():
    ds = rdata.range(100).map(lambda r: {"id": r["id"] * 3}).limit(5)
    ops = adapt_plan(optimize_plan(ds._ops))
    kinds = [type(op).__name__ for op in ops]
    # The pushed-down limit stays a driver op, BELOW (before) the map.
    assert kinds.index("LimitOperator") < kinds.index("ChannelMapStage")
    assert any(isinstance(op, LimitOperator) for op in ops)


def test_actor_pool_op_becomes_channel_stage():
    class AddBias:
        def __init__(self, bias):
            self.bias = bias

        def __call__(self, b):
            return {"id": b["id"] + self.bias}

    ds = rdata.range(16).map_batches(AddBias, fn_constructor_kwargs={
        "bias": 5}, concurrency=(2, 4))
    ops = adapt_plan(optimize_plan(ds._ops))
    stages = [op for op in ops if isinstance(op, ChannelMapStage)]
    assert len(stages) == 1
    assert stages[0].payload["fn_cls"] is AddBias
    assert 2 <= stages[0].lanes <= 4


# ------------------------------------------------------ queues (store-free)

def test_local_queue_blocks_producer_at_capacity():
    q = LocalQueue(capacity=2, name="t")
    q.put(1)
    q.put(2)
    progressed = threading.Event()

    def produce():
        q.put(3)  # must block until the consumer frees a slot
        progressed.set()

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    assert not progressed.wait(0.3), "producer ran past a full queue"
    assert q.get() == 1
    assert progressed.wait(5.0), "producer never unblocked"
    assert q.get() == 2 and q.get() == 3
    q.shutdown()


def test_local_queue_stop_drains_then_raises():
    q = LocalQueue(capacity=4, name="t")
    q.put("a")
    q.put_stop()
    assert q.get() == "a"  # backlog drains before the stop marker
    with pytest.raises(QueueStopped):
        q.get()
    q.shutdown()


def test_local_queue_shutdown_unblocks_producer():
    q = LocalQueue(capacity=1, name="t")
    q.put(1)
    done = threading.Event()

    def produce():
        q.put(2)  # consumer abandons: put must return, not hang
        done.set()

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    time.sleep(0.1)
    q.shutdown()
    assert done.wait(5.0)


def test_channel_queue_ring_backpressure():
    """The executor's edge contract on a real shm ring: capacity bounds
    frames in flight, a slow consumer blocks the producer, stop ends the
    stream."""
    cid = uuid.uuid4().bytes[:12]
    wq = ChannelQueue(RingChannel(cid, capacity=2), name="w")
    rq = ChannelQueue(RingChannel(cid, capacity=2), name="r")
    try:
        rq.prepare_read()
        wq.put((0, "a"))
        wq.put((1, "b"))
        progressed = threading.Event()

        def produce():
            wq.put((2, "c"), timeout=30.0)  # ring full: must block here
            wq.put_stop()
            progressed.set()

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        assert not progressed.wait(0.3), "producer ran past a full ring"
        assert rq.get(timeout=5.0) == (0, "a")  # frees a slot
        assert rq.get(timeout=5.0) == (1, "b")
        assert rq.get(timeout=5.0) == (2, "c")
        assert progressed.wait(5.0), "producer never unblocked"
        with pytest.raises(QueueStopped):
            rq.get(timeout=5.0)
        t.join(timeout=5.0)
    finally:
        wq.shutdown()
        rq.shutdown(unlink=True)


# --------------------------------------- exchange kernels (store-free)

def _blocks(seed, n_blocks=6, rows=40):
    rng = np.random.default_rng(seed)
    return [{"k": rng.integers(0, 17, rows), "v": rng.integers(0, 1000, rows)}
            for _ in range(n_blocks)]


def test_exchange_kernels_transport_order_identity():
    """Both transports share partition_rows/merge_pieces; the channel
    path's only freedom is piece ARRIVAL order. Reducers re-sort pieces
    by block index, so any interleaving merges identically to the task
    path's in-order waves."""
    blocks = _blocks(7)
    n_parts = 5

    def assign(block, block_index):
        return np.asarray(block["k"]) % n_parts

    split = [partition_rows(b, assign, n_parts, i)
             for i, b in enumerate(blocks)]
    # Task transport: partition j's pieces in block order.
    task_out = [merge_pieces([split[i][j] for i in range(len(blocks))],
                             None) for j in range(n_parts)]
    # Channel transport: pieces land interleaved across 3 mappers; the
    # reducer keys them by block index and sorts before merging.
    for j in range(n_parts):
        cells = {}
        for m in range(3):
            for i in range(m, len(blocks), 3):  # mapper m's stream
                cells[i] = split[i][j]
        chan = merge_pieces([cells[i] for i in sorted(cells)], None)
        assert np.array_equal(chan["k"], task_out[j]["k"])
        assert np.array_equal(chan["v"], task_out[j]["v"])


def test_partition_rows_empty_block_keeps_schema():
    empty = {"k": np.array([], dtype=np.int64)}
    parts = partition_rows(empty, lambda b, i: np.array([]), 3)
    assert len(parts) == 3
    assert all(p["k"].shape == (0,) for p in parts)


def test_train_session_iter_device_batches_delegates():
    """The train-surface ingest helper hands the shard's iter_batches the
    device + prefetch depth (the double-buffered path); plain-sequence
    shards without iter_batches are rejected up front."""
    from ray_tpu.train.config import TrainContextConfig
    from ray_tpu.train.session import TrainSession

    class FakeShard:
        def __init__(self):
            self.calls = []

        def iter_batches(self, **kw):
            self.calls.append(kw)
            return iter([{"x": np.ones(2)}])

    shard = FakeShard()
    sess = TrainSession(lambda cfg: None, {}, TrainContextConfig(),
                        dataset_shards={"train": shard, "plain": [1, 2, 3]})
    out = list(sess.iter_device_batches(
        batch_size=32, device="dev0", prefetch_depth=4))
    assert len(out) == 1
    assert shard.calls == [{"batch_size": 32, "device_put": "dev0",
                            "prefetch_depth": 4}]
    with pytest.raises(TypeError):
        sess.iter_device_batches("plain", device="dev0")
    with pytest.raises(KeyError):
        sess.iter_device_batches("missing", device="dev0")


# ------------------------------------------------------------ cluster tier

@pytest.fixture(scope="module")
def cluster(native_store):
    try:
        rt = ray_tpu.init(num_cpus=4)
    except Exception as e:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        pytest.skip(f"cluster runtime unavailable: {e!r}")
    yield rt
    ray_tpu.shutdown()


def test_streaming_matches_pull_executor(cluster):
    ds = (rdata.range(200, parallelism=8)
          .map_batches(lambda b: {"v": b["id"] * 3})
          .filter(lambda r: r["v"] % 2 == 0))
    old = cfg.data_executor
    try:
        cfg.data_executor = "pull"
        pull_rows = [r["v"] for r in ds.take_all()]
        cfg.data_executor = "streaming"
        stream_rows = [r["v"] for r in ds.take_all()]
    finally:
        cfg.data_executor = old
    assert stream_rows == pull_rows


def test_channel_vs_task_shuffle_identity(cluster):
    ds = rdata.range(300, parallelism=6).map_batches(
        lambda b: {"v": b["id"] * 7})
    old = cfg.data_exchange_transport
    try:
        cfg.data_exchange_transport = "channel"
        a = [r["v"] for r in ds.random_shuffle(seed=11).take_all()]
        cfg.data_exchange_transport = "task"
        b = [r["v"] for r in ds.random_shuffle(seed=11).take_all()]
    finally:
        cfg.data_exchange_transport = old
    assert a == b
    assert sorted(a) == [i * 7 for i in range(300)]


def test_channel_vs_task_sort_identity(cluster):
    ds = rdata.range(200, parallelism=5).map_batches(
        lambda b: {"k": (b["id"] * 37) % 41, "v": b["id"]})
    old = cfg.data_exchange_transport
    try:
        cfg.data_exchange_transport = "channel"
        a = [(r["k"], r["v"]) for r in ds.sort("k").take_all()]
        cfg.data_exchange_transport = "task"
        b = [(r["k"], r["v"]) for r in ds.sort("k").take_all()]
    finally:
        cfg.data_exchange_transport = old
    assert a == b
    assert a == sorted(a, key=lambda t: t[0])


def _slow_triple(b):
    time.sleep(0.2)  # keep lanes mid-stream long enough to kill one
    return {"v": b["id"] * 3}


def test_lane_death_mid_stream_recovers(cluster):
    """Kill one operator actor while its stage is mid-stream: the driver
    respawns the lane, replays its in-flight frames, and the output is
    row-identical to an undisturbed run."""
    ds = rdata.range(64, parallelism=8).map_batches(_slow_triple)
    expected = [r["v"] for r in ds.take_all()]

    ops = adapt_plan(optimize_plan(ds._ops))
    stage = next(op for op in ops if isinstance(op, ChannelMapStage))
    ctx = ExecContext()
    stream = InputOperator(ds._read_tasks, parallelism=8).execute(None, ctx)
    out = stage.execute(stream, ctx)
    got = []
    try:
        ref, _meta = next(out)
        got.extend(ray_tpu.get(ref)["v"].tolist())
        ray_tpu.kill(stage._live_lanes[0].actor)  # mid-stream death
        for ref, _meta in out:
            got.extend(ray_tpu.get(ref)["v"].tolist())
    finally:
        ctx.run_finalizers()
    assert got == expected
    assert any(lane.respawns for lane in stage._live_lanes)
