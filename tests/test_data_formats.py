"""Interchange-format connectors: tfrecord/Example codec, webdataset tar
shards, avro container decoding, and the from_torch/from_huggingface
interop constructors (reference analog: data/tests for tfrecords/webdataset/
avro datasources)."""

import io
import json
import struct
import zlib

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rdata
from ray_tpu.data import formats


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_crc32c_known_vectors():
    # RFC 3720 test vectors.
    assert formats.crc32c(b"") == 0x0
    assert formats.crc32c(b"123456789") == 0xE3069283
    assert formats.crc32c(bytes(32)) == 0x8A9136AA


def test_example_proto_roundtrip():
    feats = {
        "label": 3,
        "weights": [1.5, -2.25],
        "name": b"sample-1",
        "tags": [b"a", b"b", b"c"],
    }
    parsed = formats.parse_example(formats.encode_example(feats))
    assert parsed["label"] == [3]
    np.testing.assert_allclose(parsed["weights"], [1.5, -2.25])
    assert parsed["name"] == [b"sample-1"]
    assert parsed["tags"] == [b"a", b"b", b"c"]


def test_tfrecords_roundtrip_through_dataset(cluster, tmp_path):
    ds = rdata.from_numpy({
        "x": np.arange(10, dtype=np.int64),
        "y": np.linspace(0, 1, 10).astype(np.float32),
    }, parallelism=2)
    out = ds.write_tfrecords(str(tmp_path / "tfr"))
    assert out and all(p.endswith(".tfrecords") for p in out)

    back = rdata.read_tfrecords(str(tmp_path / "tfr")).materialize()
    rows = sorted(back.take_all(), key=lambda r: r["x"])
    assert [r["x"] for r in rows] == list(range(10))
    np.testing.assert_allclose([r["y"] for r in rows],
                               np.linspace(0, 1, 10), rtol=1e-6)


def test_tfrecords_crc_detects_corruption(tmp_path):
    path = str(tmp_path / "x.tfrecords")
    formats.write_tfrecord_file(path, [b"hello world"])
    raw = bytearray(open(path, "rb").read())
    raw[14] ^= 0xFF  # flip a payload byte
    open(path, "wb").write(bytes(raw))
    with pytest.raises(ValueError, match="crc"):
        list(formats.read_tfrecord_file(path))


def test_webdataset_roundtrip(cluster, tmp_path):
    ds = rdata.from_items([
        {"__key__": f"s{i}", "txt": f"caption {i}".encode(),
         "cls": str(i).encode()}
        for i in range(6)
    ], parallelism=2)
    out = ds.write_webdataset(str(tmp_path / "wds"))
    assert out and all(p.endswith(".tar") for p in out)

    back = rdata.read_webdataset(str(tmp_path / "wds")).materialize()
    rows = sorted(back.take_all(), key=lambda r: r["__key__"])
    assert [r["__key__"] for r in rows] == [f"s{i}" for i in range(6)]
    assert rows[2]["txt"] == b"caption 2"
    assert rows[2]["cls"] == b"2"


def _write_avro(path, schema: dict, rows, codec=b"null"):
    """Hand-rolled avro writer (tests only; the library reader is the
    product surface)."""
    def zig(n):
        return _varint((n << 1) ^ (n >> 63))

    def _varint(n):
        out = b""
        n &= (1 << 64) - 1
        while True:
            b = n & 0x7F
            n >>= 7
            if n:
                out += bytes([b | 0x80])
            else:
                return out + bytes([b])

    def enc(schema, v):
        if isinstance(schema, dict) and schema["type"] == "record":
            return b"".join(enc(f["type"], v[f["name"]])
                            for f in schema["fields"])
        if isinstance(schema, list):  # union: pick the matching branch
            idx = 0 if v is None else 1
            return zig(idx) + (b"" if v is None else enc(schema[idx], v))
        if schema in ("int", "long"):
            return zig(v)
        if schema == "double":
            return struct.pack("<d", v)
        if schema == "string":
            b = v.encode()
            return zig(len(b)) + b
        raise AssertionError(schema)

    body = b"".join(enc(schema, r) for r in rows)
    if codec == b"deflate":
        cobj = zlib.compressobj(wbits=-15)
        body = cobj.compress(body) + cobj.flush()
    sync = bytes(range(16))
    meta = {"avro.schema": json.dumps(schema).encode(), "avro.codec": codec}
    out = io.BytesIO()
    out.write(b"Obj\x01")
    out.write(zig(len(meta)))
    for k, v in meta.items():
        kb = k.encode()
        out.write(zig(len(kb)) + kb + zig(len(v)) + v)
    out.write(zig(0))
    out.write(sync)
    out.write(zig(len(rows)) + zig(len(body)) + body + sync)
    with open(path, "wb") as f:
        f.write(out.getvalue())


AVRO_SCHEMA = {
    "type": "record", "name": "Rec",
    "fields": [
        {"name": "id", "type": "long"},
        {"name": "score", "type": "double"},
        {"name": "tag", "type": "string"},
        {"name": "opt", "type": ["null", "long"]},
    ],
}


@pytest.mark.parametrize("codec", [b"null", b"deflate"])
def test_avro_decoding(tmp_path, codec, cluster):
    rows = [{"id": i, "score": i * 0.5, "tag": f"t{i}",
             "opt": None if i % 2 else i * 10}
            for i in range(7)]
    path = str(tmp_path / "data.avro")
    _write_avro(path, AVRO_SCHEMA, rows, codec=codec)

    decoded = formats.read_avro_file(path)
    assert decoded == rows

    ds = rdata.read_avro(path).materialize()
    got = sorted(ds.take_all(), key=lambda r: r["id"])
    assert [r["tag"] for r in got] == [f"t{i}" for i in range(7)]


def test_from_torch(cluster):
    import torch

    class DS(torch.utils.data.Dataset):
        def __len__(self):
            return 5

        def __getitem__(self, i):
            return {"x": i, "y": i * i}

    ds = rdata.from_torch(DS())
    rows = sorted(ds.materialize().take_all(), key=lambda r: r["x"])
    assert [r["y"] for r in rows] == [0, 1, 4, 9, 16]


def test_from_huggingface_via_pandas_protocol(cluster):
    import pandas as pd

    class FakeHF:  # anything exposing to_pandas (datasets.Dataset does)
        def to_pandas(self):
            return pd.DataFrame({"a": [1, 2, 3]})

    ds = rdata.from_huggingface(FakeHF())
    assert sorted(r["a"] for r in ds.materialize().take_all()) == [1, 2, 3]
