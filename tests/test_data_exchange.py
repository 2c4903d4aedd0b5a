"""Data exchange tier tests: sort / groupby / repartition / global shuffle
(reference analog: python/ray/data/tests/test_sort.py, test_all_to_all.py),
including the out-of-core sort through store spilling.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rdata


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_sort_global_order(cluster):
    rng = np.random.default_rng(0)
    ds = rdata.from_numpy({"x": rng.permutation(5000),
                           "y": np.arange(5000)}, parallelism=7)
    rows = ds.sort("x").take_all()
    xs = [r["x"] for r in rows]
    assert xs == sorted(xs)
    assert len(xs) == 5000
    # Row integrity: y still pairs with its x after the exchange.
    orig = np.random.default_rng(0).permutation(5000)
    pairs = {int(r["x"]): int(r["y"]) for r in rows}
    for x_val in (0, 1234, 4999):
        assert pairs[x_val] == int(np.flatnonzero(orig == x_val)[0])


def test_sort_descending(cluster):
    ds = rdata.from_numpy({"x": np.random.default_rng(3).normal(size=2000)},
                          parallelism=5)
    xs = [r["x"] for r in ds.sort("x", descending=True).take_all()]
    assert xs == sorted(xs, reverse=True)


def test_groupby_matches_numpy_oracle(cluster):
    rng = np.random.default_rng(1)
    k = rng.integers(0, 9, 4000)
    v = rng.normal(size=4000)
    ds = rdata.from_numpy({"k": k, "v": v}, parallelism=6)

    out = {r["k"]: r for r in ds.groupby("k").aggregate(
        ("sum", "v", "s"), ("mean", "v", "m"), ("min", "v", "lo"),
        ("max", "v", "hi"), ("std", "v", "sd"),
        ("count", None, "n")).take_all()}
    assert len(out) == 9
    for g in range(9):
        sel = v[k == g]
        np.testing.assert_allclose(out[g]["s"], sel.sum(), rtol=1e-9)
        np.testing.assert_allclose(out[g]["m"], sel.mean(), rtol=1e-9)
        np.testing.assert_allclose(out[g]["lo"], sel.min(), rtol=1e-9)
        np.testing.assert_allclose(out[g]["hi"], sel.max(), rtol=1e-9)
        np.testing.assert_allclose(out[g]["sd"], sel.std(), rtol=1e-7)
        assert out[g]["n"] == len(sel)


def test_groupby_map_groups(cluster):
    ds = rdata.from_numpy({"k": np.array([0, 1, 0, 1, 2]),
                           "v": np.array([1.0, 2.0, 3.0, 4.0, 5.0])},
                          parallelism=2)

    def top_row(block):
        i = int(np.argmax(block["v"]))
        return {c: a[i:i + 1] for c, a in block.items()}

    rows = ds.groupby("k").map_groups(top_row).take_all()
    got = {int(r["k"]): float(r["v"]) for r in rows}
    assert got == {0: 3.0, 1: 4.0, 2: 5.0}


def test_repartition_even(cluster):
    ds = rdata.range(1003, parallelism=5).repartition(3)
    sizes = [m.num_rows for _r, m in ds.iter_block_refs()]
    assert len(sizes) == 3 and sum(sizes) == 1003
    assert max(sizes) - min(sizes) <= 2


def test_global_shuffle_crosses_blocks(cluster):
    ds = rdata.range(1000, parallelism=4).random_shuffle(seed=7)
    blocks = [ray_tpu.get(r) for r, _m in ds.iter_block_refs()]
    # Multiset preserved.
    all_ids = sorted(sum((b["id"].tolist() for b in blocks), []))
    assert all_ids == list(range(1000))
    # Rows CROSS blocks: the first output block must mix input ranges
    # (input block i held [250*i, 250*(i+1)) contiguously).
    first = set(blocks[0]["id"].tolist())
    spans = [sum(1 for x in first if 250 * i <= x < 250 * (i + 1))
             for i in range(4)]
    assert sum(1 for s in spans if s > 0) >= 3, spans


def test_out_of_core_sort_through_spilling():
    """Sort ~2x the object store memory: exchange partitions spill to disk
    and restore transparently (reference: sort release tests run the same
    shape against object_store memory pressure)."""
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=2, object_store_memory=48 << 20)
    try:
        # 32 x 3MB blocks = 96MB dataset = 2x the 48MB store: the exchange's
        # intermediates (input blocks + 1024 pieces + 32 sorted outputs,
        # ~3x the dataset in flight) cannot fit and walk through spill
        # files. Blocks stay small relative to the store (the production
        # shape); per-stage wave admission bounds the pinned working set.
        n_per = 375_000
        n_blocks = 32

        def make_read(i):
            def read():
                rng = np.random.default_rng(i)
                return {"x": rng.integers(0, 1 << 30, n_per)}
            return read

        from ray_tpu.data.dataset import Dataset

        ds = Dataset([make_read(i) for i in range(n_blocks)],
                     read_parallelism=2).sort("x")
        last = None
        total = 0
        for ref, meta in ds.iter_block_refs():
            block = ray_tpu.get(ref)
            xs = block["x"]
            assert (np.diff(xs) >= 0).all(), "partition not sorted"
            if last is not None and len(xs):
                assert xs[0] >= last, "partitions out of order"
            if len(xs):
                last = xs[-1]
            total += len(xs)
            del block, xs
        assert total == n_blocks * n_per
    finally:
        ray_tpu.shutdown()
