"""Arrow block path: string/nested/null columns ride pyarrow Arrays
through the data plane — groupby/sort over a string-keyed parquet
dataset without numpy object arrays (reference analog:
python/ray/data/block.py:57 Arrow BlockAccessor backend).
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import ray_tpu
from ray_tpu import data as rdata
from ray_tpu.data.block import (BlockAccessor, col_take,
                                col_unique_inverse, is_arrow_col)


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture()
def string_parquet(tmp_path):
    """Two parquet files with a string key, a nullable string, and a
    nested list column."""
    rng = np.random.default_rng(0)
    paths = []
    for i in range(2):
        n = 50
        table = pa.table({
            "city": pa.array(rng.choice(["osaka", "kyoto", "nara"], n)),
            "note": pa.array([None if j % 7 == 0 else f"n{j}"
                              for j in range(n)]),
            "tags": pa.array([["a", "b"][: 1 + j % 2] for j in range(n)]),
            "pop": rng.integers(1, 100, n).astype(np.int64),
        })
        p = str(tmp_path / f"part{i}.parquet")
        pq.write_table(table, p)
        paths.append(p)
    return paths


def test_reader_auto_selects_arrow_columns(cluster, string_parquet):
    ds = rdata.read_parquet(string_parquet)
    block = next(ds.iter_batches(batch_size=None))
    assert is_arrow_col(block["city"]), type(block["city"])
    assert is_arrow_col(block["note"])   # nullable -> arrow
    assert is_arrow_col(block["tags"])   # nested -> arrow
    assert isinstance(block["pop"], np.ndarray)  # numeric -> numpy
    assert block["pop"].dtype == np.int64
    # NO object arrays anywhere.
    for col in block.values():
        if isinstance(col, np.ndarray):
            assert col.dtype != object


def test_string_key_groupby_without_object_arrays(cluster, string_parquet):
    ds = rdata.read_parquet(string_parquet)
    out = ds.groupby("city").sum("pop").materialize()
    rows = {r["city"]: r["sum(pop)"] for r in out.take_all()}
    # Cross-check against a host-side computation.
    t = pa.concat_tables([pq.read_table(p) for p in string_parquet])
    expect = {}
    for city, pop in zip(t["city"].to_pylist(), t["pop"].to_pylist()):
        expect[city] = expect.get(city, 0) + pop
    assert rows == expect


def test_string_key_sort_global_order(cluster, string_parquet):
    ds = rdata.read_parquet(string_parquet)
    cities = [r["city"] for r in
              ds.sort("city").materialize().take_all()]
    assert cities == sorted(cities)
    assert len(cities) == 100
    desc = [r["city"] for r in
            ds.sort("city", descending=True).materialize().take_all()]
    assert desc == sorted(desc, reverse=True)


def test_null_keys_group_and_sort(cluster, tmp_path):
    table = pa.table({
        "k": pa.array(["b", None, "a", "b", None, "a", "a"]),
        "v": np.arange(7, dtype=np.float64),
    })
    p = str(tmp_path / "nulls.parquet")
    pq.write_table(table, p)
    ds = rdata.read_parquet(p)
    counts = {r["k"]: r["count()"] for r in
              ds.groupby("k").count().materialize().take_all()}
    assert counts == {"a": 3, "b": 2, None: 2}
    srt = [r["k"] for r in ds.sort("k").materialize().take_all()]
    assert srt[:5] == ["a", "a", "a", "b", "b"]
    assert srt[5:] == [None, None]  # nulls last


def test_arrow_roundtrip_through_object_store(cluster):
    """Arrow columns survive the shm object plane (pickle-5 out-of-band
    IPC buffers) bit-exactly."""
    col = pa.array(["alpha", None, "gamma"] * 100)
    ref = ray_tpu.put({"s": col, "x": np.arange(300)})
    out = ray_tpu.get(ref)
    assert is_arrow_col(out["s"])
    assert out["s"].equals(col)


def test_arrow_shuffle_and_map_groups(cluster, string_parquet):
    ds = rdata.read_parquet(string_parquet)
    shuffled = ds.random_shuffle(seed=7).materialize()
    assert sorted(r["pop"] for r in shuffled.take_all()) == sorted(
        r["pop"] for r in rdata.read_parquet(string_parquet).take_all())

    def biggest(group):
        idx = np.argsort(np.asarray(group["pop"]))[-1:]
        return {"city": col_take(group["city"], idx),
                "pop": np.asarray(group["pop"])[idx]}

    tops = (rdata.read_parquet(string_parquet)
            .groupby("city").map_groups(biggest).materialize().take_all())
    assert len(tops) == 3


def test_write_parquet_preserves_arrow_columns(cluster, string_parquet,
                                               tmp_path):
    ds = rdata.read_parquet(string_parquet)
    outdir = str(tmp_path / "out")
    ds.write_parquet(outdir)
    back = rdata.read_parquet(outdir)
    assert sorted(r["city"] for r in back.take_all()) == sorted(
        r["city"] for r in ds.take_all())


def test_nullable_numeric_column_stays_numpy_nan(cluster, tmp_path):
    """Nullable ints/floats keep the numpy NaN representation so numeric
    consumers (aggregation, device_put) are unaffected, and sorts stay
    NUMERIC (never lexicographic)."""
    table = pa.table({
        "k": pa.array([10, 2, None, 7, 1], type=pa.int64()),
        "v": np.arange(5, dtype=np.float64),
    })
    p = str(tmp_path / "nn.parquet")
    pq.write_table(table, p)
    ds = rdata.read_parquet(p)
    block = next(ds.iter_batches(batch_size=None))
    assert isinstance(block["k"], np.ndarray)
    assert block["k"].dtype == np.float64  # NaN-filled
    srt = [r["k"] for r in ds.sort("k").materialize().take_all()]
    assert srt[:4] == [1.0, 2.0, 7.0, 10.0]  # numeric, not "10"<"2"


def test_sort_boundary_width_no_truncation(cluster, tmp_path):
    """String range boundaries must not be truncated to a block's max
    string width (searchsorted promotes widths itself)."""
    t1 = pa.table({"k": pa.array(["ban", "bag", "a"] * 10)})
    t2 = pa.table({"k": pa.array(["banana", "bananas", "zed"] * 10)})
    p1, p2 = str(tmp_path / "w1.parquet"), str(tmp_path / "w2.parquet")
    pq.write_table(t1, p1)
    pq.write_table(t2, p2)
    srt = [r["k"] for r in rdata.read_parquet([p1, p2])
           .sort("k", num_partitions=4).materialize().take_all()]
    assert srt == sorted(srt)


def test_json_csv_tfrecords_sinks_accept_arrow(cluster, string_parquet,
                                               tmp_path):
    ds = rdata.read_parquet(string_parquet, columns=["city", "pop"])
    jdir = str(tmp_path / "j")
    ds.write_json(jdir)
    back = rdata.read_json(jdir)
    assert sorted(r["city"] for r in back.take_all()) == sorted(
        r["city"] for r in ds.take_all())
    ds.write_csv(str(tmp_path / "c"))
    ds.write_tfrecords(str(tmp_path / "t"))


def test_col_unique_inverse_matches_numpy_semantics():
    col = pa.array(["b", "a", "c", "a", "b"])
    uniq, inv = col_unique_inverse(col)
    assert uniq.to_pylist() == ["a", "b", "c"]
    assert col.take(np.flatnonzero(inv == 0)).to_pylist() == ["a", "a"]
    n_uniq, n_inv = col_unique_inverse(np.array(["b", "a", "c", "a", "b"]))
    assert list(n_inv) == list(inv)
