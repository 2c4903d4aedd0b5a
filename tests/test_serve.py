"""Serve-lite: deployments, routing, batching, autoscaling, HTTP, LLM
engine (reference test model: python/ray/serve/tests/test_deploy.py,
test_batching.py, test_autoscaling_policy.py).
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
import ray_tpu.serve as serve


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=24)
    yield rt
    serve.shutdown()
    ray_tpu.shutdown()


def test_deploy_and_route(cluster):
    @serve.deployment(num_replicas=2)
    class Doubler:
        def __call__(self, x):
            return x * 2

        def plus(self, x, y=0):
            return x + y

    handle = serve.run(Doubler.bind())
    assert handle.remote(21).result() == 42
    # Named-method routing.
    assert handle.options("plus").remote(1, y=2).result() == 3
    assert handle.plus.remote(5, y=5).result() == 10
    st = serve.status()
    assert st["Doubler"]["num_replicas"] == 2


def test_redeploy_updates_code(cluster):
    @serve.deployment(name="ver")
    class V1:
        def __call__(self, _):
            return "v1"

    h = serve.run(V1.bind())
    assert h.remote(None).result() == "v1"

    @serve.deployment(name="ver")
    class V2:
        def __call__(self, _):
            return "v2"

    h = serve.run(V2.bind())
    assert h.remote(None).result() == "v2"


def test_replica_failure_rerouted(cluster):
    @serve.deployment(name="ft", num_replicas=2)
    class FT:
        def __call__(self, x):
            return x + 1

    h = serve.run(FT.bind())
    assert h.remote(1).result() == 2
    # Kill one replica; routing must recover (controller respawns it).
    controller = ray_tpu.get_actor("rtpu-serve-controller")
    replicas = ray_tpu.get(controller.get_replicas.remote("ft"), timeout=30)
    ray_tpu.kill(replicas[0])
    ok = 0
    deadline = time.time() + 60
    while ok < 5 and time.time() < deadline:
        try:
            assert h.remote(1).result(timeout=10) == 2
            ok += 1
        except Exception:
            time.sleep(0.5)
    assert ok >= 5


def test_serve_batch_collapses_calls(cluster):
    calls = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
    def compute(xs):
        calls.append(len(xs))
        return [x * 10 for x in xs]

    import concurrent.futures as cf

    with cf.ThreadPoolExecutor(8) as pool:
        outs = list(pool.map(compute, range(8)))
    assert outs == [x * 10 for x in range(8)]
    assert max(calls) > 1  # at least one real batch formed


def test_batch_in_deployment(cluster):
    @serve.deployment(max_ongoing_requests=8)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def __call__(self, xs):
            self.batch_sizes.append(len(xs))
            return [x + 100 for x in xs]

        def sizes(self):
            return self.batch_sizes

    h = serve.run(Batched.bind())
    rs = [h.remote(i) for i in range(8)]
    assert [r.result() for r in rs] == [i + 100 for i in range(8)]
    assert max(h.sizes.remote().result()) > 1


def test_autoscaling_up_and_down(cluster):
    @serve.deployment(name="auto", autoscaling_config={
        "min_replicas": 1, "max_replicas": 3,
        "target_ongoing_requests": 1})
    class Slow:
        def __call__(self, x):
            time.sleep(1.0)
            return x

    h = serve.run(Slow.bind())
    # Sustained concurrent load -> scale above 1 replica.
    import concurrent.futures as cf

    def spam(_):
        try:
            return h.remote(1).result(timeout=30)
        except Exception:
            return None

    with cf.ThreadPoolExecutor(6) as pool:
        list(pool.map(spam, range(24)))
        scaled = 0
        deadline = time.time() + 40
        while time.time() < deadline:
            scaled = serve.status()["auto"]["num_replicas"]
            if scaled > 1:
                break
            list(pool.map(spam, range(12)))
    assert scaled > 1


def test_http_proxy_end_to_end(cluster):
    @serve.deployment(name="echo")
    class Echo:
        def __call__(self, payload):
            return {"you_sent": payload}

    serve.run(Echo.bind())
    _proxy, port = serve.start_http()
    url = f"http://127.0.0.1:{port}"
    with urllib.request.urlopen(f"{url}/-/healthz", timeout=10) as r:
        assert json.load(r)["status"] == "ok"
    req = urllib.request.Request(
        f"{url}/echo", data=json.dumps({"a": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        out = json.load(r)
    assert out["result"]["you_sent"] == {"a": 1}


def test_llm_engine_continuous_batching(cluster):
    """Correctness: engine generations must match step-by-step greedy
    decode, including when requests share the engine concurrently."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = llama.tiny_config(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    engine = LLMEngine(cfg, params, max_batch=2, max_len=128,
                       prompt_buckets=[8, 16])

    def reference_greedy(prompt, n):
        import jax.numpy as jnp

        ids = list(prompt)
        for _ in range(n):
            logits = llama.forward(params, jnp.asarray([ids]), cfg)
            ids.append(int(jnp.argmax(logits[0, -1])))
        return ids[len(prompt):]

    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    import concurrent.futures as cf

    with cf.ThreadPoolExecutor(3) as pool:
        futs = [pool.submit(engine.generate, p, 6) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
    for p, o in zip(prompts, outs):
        assert o["token_ids"] == reference_greedy(p, 6), (p, o)
    engine.close()
