"""Serve streaming + multiplexing tests (reference analog:
python/ray/serve/tests/test_streaming_response.py, test_multiplex.py).
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    serve.shutdown()
    ray_tpu.shutdown()


def test_streaming_response_end_to_end(cluster):
    @serve.deployment
    class Streamer:
        def tokens(self, request):
            for i in range(request["n"]):
                time.sleep(0.02)
                yield {"tok": i}

    handle = serve.run(Streamer.bind(), name="streamer")
    gen = handle.options("tokens", stream=True).remote({"n": 8})
    # Items arrive INCREMENTALLY: the first item lands long before the
    # full stream finishes.
    t0 = time.monotonic()
    first = next(iter_ := iter(gen))
    t_first = time.monotonic() - t0
    rest = list(iter_)
    t_all = time.monotonic() - t0
    assert first == {"tok": 0}
    assert rest == [{"tok": i} for i in range(1, 8)]
    assert t_first < t_all, "stream was not incremental"
    serve.delete("streamer")


def test_streaming_error_propagates(cluster):
    @serve.deployment
    class Bad:
        def tokens(self, request):
            yield 1
            raise RuntimeError("boom mid-stream")

    handle = serve.run(Bad.bind(), name="bad-streamer")
    gen = handle.options("tokens", stream=True).remote({})
    it = iter(gen)
    assert next(it) == 1
    with pytest.raises(Exception, match="boom mid-stream"):
        list(it)
    serve.delete("bad-streamer")


def test_http_chunked_streaming(cluster):
    @serve.deployment
    class HStream:
        def tokens(self, request):
            for i in range(5):
                yield i * 10

    serve.run(HStream.bind(), name="hstream")
    _proxy, port = serve.start_http()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/hstream/tokens?stream=1",
        data=json.dumps({}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        assert resp.status == 200
        lines = [json.loads(l) for l in resp.read().decode().splitlines()]
    assert [l["item"] for l in lines] == [0, 10, 20, 30, 40]
    serve.delete("hstream")


def test_multiplexed_model_affinity_and_lru(cluster):
    import os

    @serve.deployment(num_replicas=2)
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        def load_model(self, model_id: str):
            self.loads.append(model_id)
            return {"model": model_id, "pid": os.getpid()}

        def __call__(self, request):
            model_id = serve.get_multiplexed_model_id()
            model = self.load_model(model_id)
            return {"served_by": model["model"], "pid": model["pid"],
                    "n_loads": len(self.loads)}

    handle = serve.run(MultiModel.bind(), name="mm")
    # Same model id -> same replica (affinity) and the model loads ONCE.
    outs = [handle.options(multiplexed_model_id="m1").remote({}).result(
        timeout=60) for _ in range(6)]
    assert {o["served_by"] for o in outs} == {"m1"}
    assert len({o["pid"] for o in outs}) == 1, "affinity broken"
    assert outs[-1]["n_loads"] == 1, "model reloaded despite cache"
    # LRU eviction: 3 models through one replica with cap 2 -> m1 must
    # reload after m2+m3 evict it.
    pid = outs[0]["pid"]
    for mid in ("m2", "m3"):
        # Force onto the SAME replica via affinity-less retries until pid
        # matches (2 replicas; affinity pins after first hit).
        for _ in range(12):
            o = handle.options(multiplexed_model_id=mid).remote({}).result(
                timeout=60)
            if o["pid"] == pid:
                break
    o = handle.options(multiplexed_model_id="m1").remote({}).result(
        timeout=60)
    assert o["served_by"] == "m1"
    serve.delete("mm")


def test_llm_engine_token_streaming(cluster):
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(max_batch=2, max_len=64)
    toks = list(engine.generate_stream([1, 2, 3], max_new_tokens=6))
    assert len(toks) == 6
    # Streamed tokens equal the blocking path's (deterministic decode).
    blocking = engine.generate([1, 2, 3], max_new_tokens=6)
    assert toks == blocking["token_ids"]
    engine.close()
