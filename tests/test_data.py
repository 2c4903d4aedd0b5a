"""Data-lite: streaming block pipelines (SURVEY M8-lite; reference test
model: python/ray/data/tests/test_map.py, test_streaming_executor.py).
"""

import os

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rdata


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def test_range_count_take(cluster):
    ds = rdata.range(100, parallelism=4)
    assert ds.count() == 100
    rows = ds.take(5)
    assert [r["id"] for r in rows] == [0, 1, 2, 3, 4]


def test_map_batches_tasks(cluster):
    ds = rdata.range(64).map_batches(
        lambda b: {"id": b["id"], "sq": b["id"] ** 2})
    rows = ds.take_all()
    assert len(rows) == 64
    assert all(r["sq"] == r["id"] ** 2 for r in rows)


def test_map_batches_actor_pool(cluster):
    class AddBias:
        def __init__(self, bias):
            self.bias = bias

        def __call__(self, batch):
            return {"id": batch["id"] + self.bias}

    ds = rdata.range(40).map_batches(
        AddBias, fn_constructor_kwargs={"bias": 1000}, concurrency=2)
    ids = sorted(r["id"] for r in ds.take_all())
    assert ids == list(range(1000, 1040))


def test_map_filter_flat_map_limit(cluster):
    ds = (rdata.from_items([{"x": i} for i in range(30)])
          .map(lambda r: {"x": r["x"] * 2})
          .filter(lambda r: r["x"] % 4 == 0)
          .flat_map(lambda r: [{"x": r["x"]}, {"x": -r["x"]}])
          .limit(6))
    xs = [r["x"] for r in ds.take_all()]
    assert len(xs) == 6
    assert xs[0] == 0 and xs[2] == 4 and xs[3] == -4


def test_iter_batches_rechunk_and_tail(cluster):
    ds = rdata.range(50, parallelism=3)
    batches = list(ds.iter_batches(batch_size=16))
    sizes = [len(b["id"]) for b in batches]
    assert sizes == [16, 16, 16, 2]
    assert np.concatenate([b["id"] for b in batches]).tolist() == list(range(50))
    # drop_last drops the ragged tail
    sizes = [len(b["id"]) for b in ds.iter_batches(batch_size=16,
                                                   drop_last=True)]
    assert sizes == [16, 16, 16]


def test_iter_batches_device_put(cluster):
    import jax

    ds = rdata.range(32)
    dev = jax.devices("cpu")[0]
    batches = list(ds.iter_batches(batch_size=8, device_put=dev))
    assert len(batches) == 4
    assert all(isinstance(b["id"], jax.Array) for b in batches)


def test_split_balanced(cluster):
    shards = rdata.range(100, parallelism=5).split(3)
    counts = [s.count() for s in shards]
    assert sum(counts) == 100
    assert max(counts) - min(counts) <= 34  # roughly balanced


def test_streaming_split_consumes_all_once(cluster):
    ds = rdata.range(60, parallelism=6).map_batches(
        lambda b: {"id": b["id"]})
    its = ds.streaming_split(2)
    got = []
    for it in its:
        for b in it.iter_batches(batch_size=None):
            got.extend(b["id"].tolist())
    assert sorted(got) == list(range(60))


def test_read_csv_json(cluster, tmp_path):
    csv_path = os.path.join(tmp_path, "t.csv")
    with open(csv_path, "w") as f:
        f.write("a,b\n1,2\n3,4\n")
    ds = rdata.read_csv(csv_path)
    rows = ds.take_all()
    assert rows[0]["a"] == 1.0 and rows[1]["b"] == 4.0

    jl = os.path.join(tmp_path, "t.jsonl")
    with open(jl, "w") as f:
        f.write('{"x": 1}\n{"x": 2}\n')
    assert [r["x"] for r in rdata.read_json(jl).take_all()] == [1, 2]


def test_read_parquet_roundtrip(cluster, tmp_path):
    pq = pytest.importorskip("pyarrow.parquet")
    import pyarrow as pa

    path = os.path.join(tmp_path, "t.parquet")
    pq.write_table(pa.table({"v": list(range(10))}), path)
    ds = rdata.read_parquet(path)
    assert ds.count() == 10
    assert sorted(r["v"] for r in ds.take_all()) == list(range(10))


def test_random_shuffle_preserves_multiset(cluster):
    ds = rdata.range(40, parallelism=2).random_shuffle(seed=0)
    assert sorted(r["id"] for r in ds.take_all()) == list(range(40))


def test_materialize_reiterable(cluster):
    mat = rdata.range(20).map_batches(
        lambda b: {"id": b["id"] + 1}).materialize()
    assert mat.count() == 20
    assert mat.count() == 20  # second pass works (blocks pinned)
    assert mat.num_blocks() >= 1


def test_dataset_feeds_trainer(cluster, tmp_path):
    """Data-lite -> Train-lite integration (VERDICT r1 'done' criterion)."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    import ray_tpu.train as train

    ds = rdata.range(64).map_batches(lambda b: {"id": b["id"]})
    out_dir = str(tmp_path)

    def loop(config):
        it = train.get_dataset_shard("train")
        rank = train.get_context().get_world_rank()
        total, nrows = 0, 0
        for batch in it.iter_batches(batch_size=8):
            total += int(batch["id"].sum())
            nrows += len(batch["id"])
        with open(os.path.join(out_dir, f"total_{rank}"), "w") as f:
            f.write(f"{total} {nrows}")
        train.report({"total": total})

    res = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="data-train", storage_path=str(tmp_path)),
        datasets={"train": ds},
    ).fit()
    assert res.error is None
    totals, rows = zip(*(
        map(int, open(os.path.join(out_dir, f"total_{r}")).read().split())
        for r in range(2)))
    # Disjoint shares covering the whole dataset exactly once.
    assert sum(totals) == sum(range(64))
    assert sum(rows) == 64
