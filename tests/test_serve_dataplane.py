"""Asyncio data plane, long-poll replica push, composition, per-node
proxies (reference test model: python/ray/serve/tests/test_proxy.py,
test_handle.py composition tests, test_long_poll.py)."""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    serve.shutdown()
    ray_tpu.shutdown()


def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_two_deployment_composition(cluster):
    """A deployment takes another deployment's handle via .bind() and
    calls it per request (reference: deployment-graph handle injection)."""

    @serve.deployment(name="embedder")
    class Embedder:
        def __call__(self, payload):
            return {"vec": [len(str(payload.get("text", "")))] * 3}

    @serve.deployment(name="ranker")
    class Ranker:
        def __init__(self, embedder):
            self._embedder = embedder

        def __call__(self, payload):
            vec = self._embedder.remote(payload).result(timeout=30)["vec"]
            return {"score": sum(vec), "via": "embedder"}

    h = serve.run(Ranker.bind(Embedder.bind()))
    out = h.remote({"text": "hello"}).result(timeout=60)
    assert out == {"score": 15, "via": "embedder"}
    # The sub-deployment is individually addressable too.
    eh = serve.get_deployment_handle("embedder")
    assert eh.remote({"text": "xy"}).result(timeout=30)["vec"] == [2, 2, 2]
    serve.delete("ranker")
    serve.delete("embedder")


def test_long_poll_pushes_replica_changes(cluster):
    """Scale-up must reach routers via long-poll push (bounded by one RPC
    round + reconcile), not a refresh timer."""

    @serve.deployment(name="lp", num_replicas=1)
    class LP:
        def __call__(self, payload):
            import os

            return {"pid": os.getpid()}

    h = serve.run(LP.bind())
    assert "pid" in h.remote({}).result(timeout=30)
    router = h._router
    v0 = router._version
    # Scale to 3 via redeploy; the router must observe the new set via its
    # long-poll thread WITHOUT any routing call forcing a refresh.
    serve.run(LP.options(num_replicas=3).bind())
    deadline = time.time() + 15
    while time.time() < deadline:
        with router._lock:
            if len(router._replicas) == 3 and router._version != v0:
                break
        time.sleep(0.1)
    with router._lock:
        n, v = len(router._replicas), router._version
    assert n == 3 and v != v0, (n, v, v0)
    serve.delete("lp")


def test_proxy_concurrency_latency(cluster):
    """The asyncio proxy must hold p50 under concurrency: with a 50ms
    handler and 64 concurrent clients over 8 replicas x 8 ongoing, p50
    must stay within 2x of the sequential p50 (thread-per-request stdlib
    ingress fails this by an order of magnitude).

    Bounded retry window (the PR 6 locality-test idiom): on a loaded
    2-core box ambient CPU alone straddles the absolute threshold, so
    the measurement gets up to 3 attempts and passes on the FIRST one
    under the bound — a broken (thread-per-request-shaped) proxy misses
    by ~10x on every attempt and still fails all three."""

    @serve.deployment(name="slow", num_replicas=8, max_ongoing_requests=8,
                      ray_actor_options={"num_cpus": 0})
    class Slow:
        def __call__(self, payload):
            time.sleep(0.05)
            return {"ok": True}

    serve.run(Slow.bind())
    _proxy, port = serve.start_http()
    url = f"http://127.0.0.1:{port}/slow"
    # Warm (replica spin-up, handle caches).
    for _ in range(4):
        _post(url, {})

    def latency_once():
        t0 = time.perf_counter()
        assert _post(url, {})["result"]["ok"] is True
        return time.perf_counter() - t0

    def measure_once():
        seq = sorted(latency_once() for _ in range(10))
        p50_seq = seq[len(seq) // 2]
        lat: list = []
        lock = threading.Lock()

        def worker(n):
            for _ in range(n):
                t = latency_once()
                with lock:
                    lat.append(t)

        threads = [threading.Thread(target=worker, args=(4,))
                   for _ in range(64)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lat.sort()
        p50_conc = lat[len(lat) // 2]
        return p50_seq, p50_conc, wall

    # 64 clients x 4 reqs x 50ms over 64 effective slots: ideal ~0.2s
    # wall; the proxy passes when p50 holds within 2x sequential.
    attempts = []
    for _ in range(3):
        p50_seq, p50_conc, wall = measure_once()
        attempts.append((p50_seq, p50_conc, wall))
        if p50_conc < max(2 * p50_seq, 0.5):
            break
        time.sleep(1.0)  # let ambient load pass before re-measuring
    else:
        raise AssertionError(
            f"p50 over bound on all attempts: {attempts}")
    serve.delete("slow")


def test_per_node_proxies(cluster):
    """start_http_per_node puts one proxy on every alive node and answers
    requests through each (reference: ProxyStateManager)."""

    @serve.deployment(name="echo2")
    class Echo2:
        def __call__(self, payload):
            return {"echo": payload.get("v")}

    from ray_tpu.util import state as state_api

    serve.run(Echo2.bind())
    proxies = serve.start_http_per_node()
    nodes = [n for n in state_api.list_nodes()
             if n.get("alive", True)]
    assert len(proxies) == len(nodes) >= 1, (proxies, nodes)
    for _nid, addr in proxies.items():
        out = _post(f"http://{addr}/echo2", {"v": 42})
        assert out["result"]["echo"] == 42
    serve.delete("echo2")


def test_grpc_ingress_unary_and_streaming(cluster):
    """gRPC ingress (reference: serve's gRPC proxy/grpc_util): unary +
    server-streaming through generic handlers, NOT_FOUND for unknown
    deployments."""
    grpc = pytest.importorskip("grpc")

    @serve.deployment(name="gsvc")
    class GSvc:
        def __call__(self, p):
            return {"doubled": p.get("n", 0) * 2}

        def gen(self, p):
            for i in range(p.get("k", 3)):
                yield {"i": i}

    serve.run(GSvc.bind())
    _proxy, port = serve.start_grpc()
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")

    unary = chan.unary_unary("/ray_tpu.serve/gsvc",
                             request_serializer=bytes,
                             response_deserializer=bytes)
    out = json.loads(unary(json.dumps({"n": 21}).encode(), timeout=60))
    assert out["result"]["doubled"] == 42

    stream = chan.unary_stream("/ray_tpu.serve/gsvc.gen",
                               request_serializer=bytes,
                               response_deserializer=bytes)
    frames = [json.loads(f) for f in stream(
        json.dumps({"k": 4}).encode(), timeout=60,
        metadata=(("rtpu-stream", "1"),))]
    assert [f["item"]["i"] for f in frames] == [0, 1, 2, 3]

    missing = chan.unary_unary("/ray_tpu.serve/nosuchdep",
                               request_serializer=bytes,
                               response_deserializer=bytes)
    with pytest.raises(grpc.RpcError) as ei:
        missing(b"{}", timeout=60)
    assert ei.value.code() == grpc.StatusCode.NOT_FOUND
    chan.close()
    serve.delete("gsvc")


def test_yaml_declarative_deploy(cluster, tmp_path):
    """serve.deploy_config: YAML applications with import_path + per-
    deployment overrides (reference: ServeDeploySchema + `serve deploy`)."""
    cfg_path = tmp_path / "serve.yaml"
    cfg_path.write_text("""
applications:
  - name: calc
    import_path: tests.serve_app_fixture:build
    args: {bias: 100}
    deployments:
      - name: Adder
        num_replicas: 2
        ray_actor_options: {num_cpus: 0}
      - name: Front
        max_ongoing_requests: 4
""")
    handles = serve.deploy_config(str(cfg_path))
    assert set(handles) == {"calc"}
    out = handles["calc"].remote({"x": 1}).result(timeout=60)
    assert out == {"front": True, "sum": 101}
    # Overrides landed: Adder scaled to 2 replicas.
    status = serve.status()
    assert status["Adder"]["num_replicas"] == 2
    # Bound-graph form (module attr `app`) deploys too.
    handles2 = serve.deploy_config(
        {"applications": [{"name": "calc2",
                           "import_path":
                               "tests.serve_app_fixture:app"}]})
    out2 = handles2["calc2"].remote({"x": 2}).result(timeout=60)
    assert out2 == {"front": True, "sum": 7}
    for name in ("calc", "calc2", "Adder", "Front"):
        try:
            serve.delete(name)
        except Exception:
            pass
